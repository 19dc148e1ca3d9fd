"""Gradient-based knob search: jax.grad vs finite differences through the
Eq. 1-11 kernel, and projected-Adam recovery of dense-grid minima."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import optimize, sweep
from repro.core.handtracking import build_detnet, build_keynet

N_DET = len(build_detnet().layers)
N_ALL = N_DET + len(build_keynet().layers)
CONFIG = dict(cut=N_DET, agg_node="7nm", sensor_node="16nm",
              weight_mem="sram")


def central_diff(objective, knob, x0, eps, **config):
    hi = optimize.evaluate(objective, {knob: x0 + eps}, **config)
    lo = optimize.evaluate(objective, {knob: x0 - eps}, **config)
    return (hi - lo) / (2 * eps)


class TestGradient:
    @pytest.mark.parametrize("knob,x0,eps", [
        ("mipi_energy_scale", 1.0, 1e-4),
        ("detnet_fps", 10.0, 1e-3),
        ("keynet_fps", 30.0, 1e-3),
        ("camera_fps", 30.0, 1e-3),
    ])
    def test_grad_avg_power_matches_finite_differences(self, knob, x0, eps):
        """The issue's acceptance check: d avg_power / d knob from jax.grad
        agrees with a float64 central difference."""
        _, g = optimize.gradient("avg_power", {knob: x0}, **CONFIG)
        fd = central_diff("avg_power", knob, x0, eps, **CONFIG)
        assert g[knob] == pytest.approx(fd, rel=1e-5, abs=1e-18)

    def test_grad_weighted_objective_matches_fd(self):
        obj = {"avg_power": 1.0, "latency": 10.0}
        _, g = optimize.gradient(obj, {"camera_fps": 30.0}, **CONFIG)
        fd = central_diff(obj, "camera_fps", 30.0, 1e-3, **CONFIG)
        assert g["camera_fps"] == pytest.approx(fd, rel=1e-5)

    def test_mipi_power_gradient_is_eq5_slope(self):
        """d P / d mipi_energy_scale is exactly the MIPI power at scale 1
        (Eq. 5 is linear in the energy/byte)."""
        v, g = optimize.gradient("avg_power", {"mipi_energy_scale": 1.0},
                                 **CONFIG)
        fields = optimize.evaluate_fields({"mipi_energy_scale": 1.0},
                                          **CONFIG)
        assert g["mipi_energy_scale"] == pytest.approx(fields["mipi"],
                                                       rel=1e-9)
        assert g["mipi_energy_scale"] > 0
        assert v == pytest.approx(fields["avg_power"], rel=1e-12)

    def test_gradient_default_point_respects_pinned_knobs(self):
        """gradient() with knobs omitted must evaluate at config-pinned
        knob values, not the global defaults."""
        v, _ = optimize.gradient("avg_power", cut=N_DET, detnet_fps=15.0)
        assert v == pytest.approx(
            optimize.evaluate("avg_power", cut=N_DET, detnet_fps=15.0),
            rel=1e-12)

    def test_raw_objective_fn_is_differentiable(self):
        f = optimize.objective_fn("avg_power", **CONFIG)
        with jax.enable_x64(True):
            g = jax.grad(lambda s: f({"mipi_energy_scale": s}))(
                jnp.asarray(1.0))
            assert np.isfinite(float(g))


class TestProjectedAdam:
    def test_monotone_knob_rides_projection_to_bound(self):
        """Pure power is monotone in detnet_fps: the optimum sits on the
        lower bound, and the dense grid agrees."""
        res = optimize.optimize_knobs({"detnet_fps": (5.0, 30.0)},
                                      "avg_power", steps=120, **CONFIG)
        gk, gv = optimize.grid_argmin({"detnet_fps": (5.0, 30.0)},
                                      "avg_power", n=26, **CONFIG)
        assert res.knobs["detnet_fps"] == pytest.approx(5.0, abs=1e-6)
        assert res.knobs["detnet_fps"] == pytest.approx(gk["detnet_fps"],
                                                        abs=1.0)
        assert res.objective <= gv * (1 + 1e-9)

    def test_recovers_dense_grid_optimum_2d(self):
        """The acceptance criterion: gradient search lands on the dense-grid
        optimum of a weighted (power, latency) objective over two knobs, to
        within grid resolution."""
        bounds = {"detnet_fps": (5.0, 30.0), "camera_fps": (20.0, 60.0)}
        obj = {"avg_power": 1.0, "latency": 10.0}
        n = 41
        res = optimize.optimize_knobs(bounds, obj, steps=250, **CONFIG)
        gk, gv = optimize.grid_argmin(bounds, obj, n=n, **CONFIG)
        for k in bounds:
            spacing = (bounds[k][1] - bounds[k][0]) / (n - 1)
            assert abs(res.knobs[k] - gk[k]) <= spacing, (k, res.knobs, gk)
        # The continuous optimum can only improve on the grid's resolution.
        assert res.objective <= gv * (1 + 1e-9)

    def test_trajectory_improves_and_fields_consistent(self):
        res = optimize.optimize_knobs(
            {"camera_fps": (20.0, 60.0)},
            {"avg_power": 1.0, "latency": 10.0}, steps=100, **CONFIG)
        assert res.trajectory.shape == (101,)
        assert res.objective <= res.trajectory[0]
        assert res.objective == pytest.approx(
            res.fields["avg_power"] + 10.0 * res.fields["latency"],
            rel=1e-9)
        # within bounds
        assert 20.0 <= res.knobs["camera_fps"] <= 60.0

    def test_init_is_respected_and_projected(self):
        res = optimize.optimize_knobs({"detnet_fps": (5.0, 30.0)},
                                      steps=5, init={"detnet_fps": 500.0},
                                      **CONFIG)
        assert 5.0 <= res.knobs["detnet_fps"] <= 30.0


class TestValidation:
    def test_rejects_unknown_knob_objective_config(self):
        with pytest.raises(ValueError, match="unknown knobs"):
            optimize.optimize_knobs({"warp_factor": (0, 1)}, cut=N_DET)
        with pytest.raises(ValueError, match="objective channels"):
            optimize.objective_fn("speed_of_light", cut=N_DET)
        with pytest.raises(ValueError, match="unknown config"):
            optimize.objective_fn("avg_power", cut=N_DET, sensor_mem="x")
        with pytest.raises(ValueError, match="cut"):
            optimize.objective_fn("avg_power", cut=N_ALL + 5)
        with pytest.raises(ValueError, match="degenerate"):
            optimize.optimize_knobs({"detnet_fps": (5.0, 5.0)}, cut=N_DET)
        with pytest.raises(ValueError):
            optimize.optimize_knobs({}, cut=N_DET)

    def test_rejects_mram_without_test_vehicle_eagerly(self):
        with pytest.raises(ValueError, match="MRAM"):
            optimize.objective_fn("avg_power", cut=N_DET,
                                  sensor_node="7nm", weight_mem="mram")
        # ...but centralized (cut 0) never builds a sensor site
        optimize.objective_fn("avg_power", cut=0, sensor_node="7nm",
                              weight_mem="mram")

    def test_evaluate_matches_grid_engine(self):
        v = optimize.evaluate("avg_power", {"detnet_fps": 12.5}, **CONFIG)
        ref = sweep.evaluate_one(N_DET, sensor_node="16nm",
                                 detnet_fps=12.5)["avg_power"]
        assert v == pytest.approx(ref, rel=1e-12)
