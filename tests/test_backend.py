"""Evaluation-backend layer: registry, parity matrix, scan fusion.

Every registered backend must reproduce the reference 10,880-grid
``StreamResult`` deliverables — argmin, top-k, channel bounds,
feasibility counts, and the exact Pareto front — against the dense
path, including the Pallas backend in interpret mode and scan-fused
dispatch (``scan_chunks`` ∈ {1, 4}) with a non-dividing chunk size.
The XLA/Pallas lowerings agree bitwise on this grid (asserted); the
documented contract is ≤1e-6.
"""

import numpy as np
import pytest

import jax

from repro.core import backend as B
from repro.core import pareto, partition, stream, sweep
from repro.core.handtracking import build_detnet, build_keynet

# The 10,880-config reference grid (lockstep with tests/test_stream.py
# and benchmarks/sweep_bench.py::GRID).
REFERENCE_GRID = dict(
    agg_nodes=("7nm", "16nm"),
    sensor_nodes=("7nm", "16nm"),
    weight_mems=("sram", "mram"),
    detnet_fps=(5.0, 10.0, 15.0, 20.0, 30.0),
    keynet_fps=(15.0, 30.0),
    num_cameras=(2, 4),
    mipi_energy_scale=(1.0, 2.0),
)

TOP_K = 4


@pytest.fixture(scope="module")
def dense():
    return sweep.evaluate_grid(**REFERENCE_GRID)


@pytest.fixture(scope="module")
def dense_front(dense):
    return pareto.pareto_front(dense)


class TestRegistry:
    def test_available_backends(self):
        names = B.available_backends()
        assert "xla" in names and "pallas" in names

    def test_default_is_xla(self):
        assert B.get_backend(None).name == "xla"
        assert B.get_backend().name == B.DEFAULT_BACKEND == "xla"

    def test_unknown_backend_raises_naming_available(self):
        with pytest.raises(ValueError, match="xla"):
            B.get_backend("cuda")
        with pytest.raises(ValueError, match="unknown"):
            stream.stream_grid(cuts=(0, 1), backend="nope")
        with pytest.raises(ValueError, match="unknown"):
            sweep.evaluate_grid(cuts=(0, 1), backend="nope")

    def test_pallas_registers_lazily(self):
        be = B.get_backend("pallas")
        assert be.name == "pallas"
        assert B.get_backend("pallas") is be

    def test_optimal_partition_validates_backend(self):
        with pytest.raises(ValueError, match="unknown"):
            partition.optimal_partition(backend="nope")
        with pytest.raises(ValueError, match="scalar"):
            partition.optimal_partition(engine="scalar", backend="xla")

    def test_optimal_partition_backend_plumbing(self):
        ref = partition.optimal_partition(sensor_node=("7nm", "16nm"))
        via = partition.optimal_partition(sensor_node=("7nm", "16nm"),
                                          backend="xla")
        assert via.cut == ref.cut and via.avg_power == ref.avg_power

    def test_scalar_fallback_rejects_explicit_backend(self):
        """A custom TechNode outside the registry falls back to the
        scalar engine, which must not silently ignore backend=."""
        import dataclasses

        from repro.core.constants import TECH_NODES
        custom = dataclasses.replace(TECH_NODES["7nm"])
        assert partition.optimal_partition(sensor_node=custom).cut >= 0
        with pytest.raises(ValueError, match="scalar"):
            partition.optimal_partition(sensor_node=custom, backend="xla")

    def test_pallas_falls_back_to_one_device_on_multidevice_hosts(self):
        """An auto-derived multi-device list must not crash a non-pmap
        backend; an explicit one must raise clearly."""
        import os
        import subprocess
        import sys

        code = """
import jax
from repro.core import stream, sweep
assert len(jax.local_devices()) == 2
res = stream.stream_grid(cuts=(0, 1, 2), backend="pallas")
assert res.n_devices == 1
assert res.argmin() == sweep.evaluate_grid(cuts=(0, 1, 2)).argmin()
try:
    stream.stream_grid(cuts=(0, 1, 2), backend="pallas",
                       devices=jax.local_devices())
except ValueError as e:
    assert "pmap" in str(e)
else:
    raise SystemExit("explicit multi-device pallas should raise")
print("PALLAS-FALLBACK-OK")
"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=2")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "PALLAS-FALLBACK-OK" in out.stdout


# The full matrix: every backend × scan depth must reproduce the dense
# deliverables with a chunk size that does not divide the grid.
@pytest.fixture(scope="module",
                params=[(be, k) for be in ("xla", "pallas")
                        for k in (1, 4)],
                ids=lambda p: f"{p[0]}-scan{p[1]}")
def streamed(request, dense):
    be, scan = request.param
    return stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                              top_k=TOP_K, track="all", backend=be,
                              scan_chunks=scan)


class TestBackendParityMatrix:
    def test_argmin_every_channel(self, streamed, dense):
        for field in sweep.FIELDS:
            assert streamed.argmin(field) == dense.argmin(field), field

    def test_top_k(self, streamed, dense):
        for obj in streamed.objectives:
            assert streamed.top_k(obj) == dense.top_k(obj, TOP_K), obj

    def test_pareto_front(self, streamed, dense_front):
        sf = streamed.pareto_front()
        assert np.array_equal(sf.indices, dense_front.indices)
        assert np.array_equal(sf.values, dense_front.values)

    def test_counts_and_bounds(self, streamed, dense):
        for field in sweep.FIELDS:
            assert streamed.finite_counts[field] == \
                int(np.isfinite(dense.data[field]).sum()), field
            assert streamed.channel_bounds(field) == \
                dense.channel_bounds(field), field

    def test_scan_depth_recorded(self, streamed):
        assert streamed.stats["scan_chunks"] in (1.0, 4.0)
        assert "dispatch_s" in streamed.stats
        assert "steps_per_s" in streamed.stats


class TestScanFusion:
    def test_auto_scan_kicks_in_on_many_steps(self, dense):
        # 10,880 / 256 ≈ 43 raw steps -> auto K > 1.
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=256)
        assert res.stats["scan_chunks"] > 1.0
        assert res.argmin() == dense.argmin()

    def test_small_grids_stay_unfused(self):
        res = stream.stream_grid(cuts=(0, 1, 2))
        assert res.stats["scan_chunks"] == 1.0

    def test_scan_clamped_to_step_count(self, dense):
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=4096,
                                 scan_chunks=64)
        assert res.stats["scan_chunks"] <= 3.0
        assert res.argmin() == dense.argmin()

    def test_scan_with_constraints_and_prefetch(self, dense):
        budget = {"latency":
                  float(np.nanquantile(dense.data["latency"], 0.4))}
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                                 scan_chunks=4, prefetch=4,
                                 constraints=budget)
        dc = dense.constrain(budget)
        assert res.argmin() == dc.argmin()
        cf, dcf = res.pareto_front(), pareto.pareto_front(dc)
        assert np.array_equal(cf.indices, dcf.indices)
        assert np.array_equal(cf.values, dcf.values)


class TestDenseBackend:
    def test_evaluate_grid_pallas_matches_xla(self):
        kw = dict(sensor_nodes=("7nm", "16nm"),
                  weight_mems=("sram", "mram"), detnet_fps=(5.0, 30.0))
        a = sweep.evaluate_grid(**kw)
        b = sweep.evaluate_grid(**kw, backend="pallas")
        for f in sweep.FIELDS:
            assert np.array_equal(a.data[f], b.data[f], equal_nan=True), f

    def test_pallas_stacked_models(self):
        det, key = build_detnet(), build_keynet()
        pairs = ((det, key), (det.scaled(0.5), key))
        a = sweep.evaluate_grid(models=pairs, detnet_fps=(10.0, 30.0))
        b = stream.stream_grid(models=pairs, detnet_fps=(10.0, 30.0),
                               chunk_size=31, backend="pallas")
        for o in b.objectives:
            assert a.argmin(o) == b.argmin(o), o

    def test_pallas_maximize_and_d1(self, dense):
        rm = stream.stream_grid(
            **REFERENCE_GRID, chunk_size=997, backend="pallas",
            objectives=("avg_power", "sensor_macs_per_s"),
            maximize=("sensor_macs_per_s",))
        macs = dense.data["sensor_macs_per_s"]
        best = rm.top_k("sensor_macs_per_s")[0]
        assert best["sensor_macs_per_s"] == float(np.nanmax(macs))
        r1 = stream.stream_grid(cuts=(0, 17, 33), backend="pallas",
                                objectives=("avg_power",))
        one = sweep.evaluate_grid(cuts=(0, 17, 33))
        assert r1.argmin() == one.argmin()


class TestPallasPlatform:
    @pytest.mark.parametrize("platform", ["tpu", "gpu"])
    def test_refuses_every_platform_but_the_cpu(self, monkeypatch,
                                                platform):
        # No compiled Mosaic lowering yet: off the CPU the step build
        # fails clearly instead of interpreting on the host.
        from repro.kernels.sweep_grid import PallasGridBackend

        S, axis_vals, _ = sweep.build_axes(cuts=(0, 17))
        shape = tuple(a.size for a in axis_vals)
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        with pytest.raises(NotImplementedError, match="Mosaic") as ei:
            PallasGridBackend().build_dense_eval(S, shape, ("avg_power",))
        assert "float64" in str(ei.value) and "(8, 128)" in str(ei.value)


class TestPallasKernelOracle:
    def test_chunk_partials_match_xla_reference(self):
        """The fused pallas_call must reproduce every block partial of
        the shared reference expression (`backend.chunk_partials`)."""
        from repro.kernels import sweep_grid

        import jax.numpy as jnp

        S, axis_vals, _ = sweep.build_axes(sensor_nodes=("7nm", "16nm"),
                                           weight_mems=("sram", "mram"))
        shape = tuple(a.size for a in axis_vals)
        n_total = int(np.prod(shape))
        spec = B.ChunkSpec(
            S=S, shape=shape, n_total=n_total, chunk=96,
            fields=tuple(pareto.DEFAULT_OBJECTIVES), d=3, k=4,
            sign=(1.0, 1.0, 1.0), cons_static=(), hist_bins=0,
            survivor_cap=96, small_index=True)
        with jax.enable_x64(True):
            axvals = tuple(map(jnp.asarray, axis_vals))
            filt = pareto.build_dominance_filter(
                np.empty((0, 3)), 3, spec.filter_rows, spec.filter_bins)
            aux = {"filter": jax.tree_util.tree_map(jnp.asarray, filt)}
            ref = sweep_grid.chunk_partials_ref(spec, axvals, aux,
                                                jnp.int64(32))
            got = sweep_grid.build_chunk_call(spec, interpret=True)(
                axvals, aux, jnp.int64(32))
        for key in ref:
            assert np.array_equal(np.asarray(ref[key]),
                                  np.asarray(got[key]),
                                  equal_nan=True), key


class TestInt64Decode:
    """Satellite: >2^31-config spaces must not overflow int32 anywhere
    in the flat-index arithmetic (synthetic 10^10-config shape)."""

    SHAPE = (10,) * 10          # 10^10 configs — far beyond int32

    def test_numpy_decode_matches_unravel_index(self):
        flat = np.array([0, 2**31 - 1, 2**31, 2**33 + 12345,
                         10**10 - 1], np.int64)
        ours = sweep.decode_flat_index(self.SHAPE, flat)
        ref = np.unravel_index(flat, self.SHAPE)
        for a, b in zip(ours, ref):
            assert np.array_equal(a, b)

    def test_int32_input_is_promoted(self):
        # A narrow flat-index array on a huge shape must be widened
        # before the stride arithmetic, not wrapped.
        flat32 = np.array([7, 2**31 - 1], np.int32)
        ours = sweep.decode_flat_index(self.SHAPE, flat32)
        ref = np.unravel_index(flat32.astype(np.int64), self.SHAPE)
        for a, b in zip(ours, ref):
            assert np.array_equal(a, b)
            assert a.dtype == np.int64

    def test_traced_decode_beyond_int32(self):
        import jax.numpy as jnp

        with jax.enable_x64(True):
            flat = jnp.asarray([2**33 + 12345, 10**10 - 1], jnp.int64)
            ours = [np.asarray(c)
                    for c in jax.jit(
                        lambda f: sweep.decode_flat_index(self.SHAPE, f)
                    )(flat)]
        ref = np.unravel_index(np.asarray([2**33 + 12345, 10**10 - 1]),
                               self.SHAPE)
        for a, b in zip(ours, ref):
            assert np.array_equal(a, b)

    def test_python_int_decode(self):
        assert sweep.decode_flat_index(self.SHAPE, 10**10 - 1) == (9,) * 10

    def test_chunk_start_arithmetic_stays_int64(self):
        """The executor's ChunkSpec must refuse int32 decode once the
        index space (plus the per-dispatch overshoot) nears 2^31."""
        spec = B.ChunkSpec(
            S=None, shape=self.SHAPE, n_total=10**10, chunk=1 << 17,
            fields=("avg_power",), d=1, k=4, sign=(1.0,),
            cons_static=(), hist_bins=0, survivor_cap=64,
            small_index=False)
        assert spec.padded >= spec.chunk
        # config_from_flat round-trips a >int32 flat index exactly.
        from collections import OrderedDict
        axes = OrderedDict((f"ax{i}", tuple(range(10)))
                           for i in range(10))
        cfg = sweep.config_from_flat(self.SHAPE, axes, 2**33 + 12345)
        strides = [10**i for i in reversed(range(10))]
        expect = [(2**33 + 12345) // s % 10 for s in strides]
        assert [cfg[f"ax{i}"] for i in range(10)] == expect


class TestJobSignature:
    """Tentpole satellite: the resumable-sweep signature must change
    with anything that changes reduction semantics, and must *not*
    change with knobs that only shape the traced computation."""

    @staticmethod
    def _spec(**overrides):
        import dataclasses
        S, axis_vals, _ = sweep.build_axes(
            sensor_nodes=("7nm", "16nm"), weight_mems=("sram", "mram"))
        shape = tuple(a.size for a in axis_vals)
        spec = B.ChunkSpec(
            S=S, shape=shape, n_total=int(np.prod(shape)), chunk=96,
            fields=tuple(pareto.DEFAULT_OBJECTIVES), d=3, k=4,
            sign=(1.0, 1.0, 1.0), cons_static=(), hist_bins=0,
            survivor_cap=96, small_index=True)
        return dataclasses.replace(spec, **overrides), axis_vals

    def _sig(self, spec=None, axis_vals=None, backend=None,
             scan_chunks=1, cons=(), hist_ranges=None, **overrides):
        if spec is None:
            spec, av = self._spec(**overrides)
            axis_vals = av if axis_vals is None else axis_vals
        return B.job_signature(spec, backend, scan_chunks, cons,
                               axis_vals, hist_ranges)

    def test_deterministic_across_rebuilds(self):
        """Rebuilding the identical spec from scratch (fresh model
        stack arrays included) yields the identical signature."""
        assert self._sig() == self._sig()
        assert len(self._sig()) == 64        # sha256 hexdigest

    def test_semantic_knobs_change_the_signature(self):
        base = self._sig()
        assert self._sig(chunk=64) != base
        assert self._sig(k=5) != base
        assert self._sig(hist_bins=8) != base
        assert self._sig(sign=(1.0, 1.0, -1.0)) != base
        assert self._sig(scan_chunks=4) != base
        assert self._sig(backend="pallas") != base
        assert self._sig(cons=(("latency", "<=", 1e-3),)) != base
        assert self._sig(hist_ranges={"avg_power": (0.0, 1.0)}) != base

    def test_axis_values_change_the_signature(self):
        spec, axis_vals = self._spec()
        base = self._sig(spec=spec, axis_vals=axis_vals)
        bumped = list(axis_vals)
        bumped[-1] = np.asarray(bumped[-1]) * 2.0
        assert self._sig(spec=spec, axis_vals=tuple(bumped)) != base

    def test_trace_only_knobs_do_not_invalidate(self):
        """survivor_cap / small_index shape only the traced computation
        (overflow falls back to an exact host re-derivation), so they
        must not orphan existing checkpoints."""
        base = self._sig()
        assert self._sig(survivor_cap=48) == base
        assert self._sig(small_index=False) == base

    def test_default_backend_is_canonicalized(self):
        """backend=None and the explicit default name must agree, so a
        resume that spells the default out loud still matches."""
        assert self._sig(backend=None) == \
            self._sig(backend=B.DEFAULT_BACKEND)
