"""Streaming executor: exact dense parity, stacked workloads, sharding.

The streaming path (`repro.core.stream.stream_grid`) must reproduce the
dense path (`repro.core.sweep.evaluate_grid`) *exactly* — argmin, top-k,
Pareto front, and validity counts — on the 10,880-config reference grid,
across chunk sizes including ones that do not divide the grid.  Stacked
workload batches are pinned to <=1e-6 against their single-model grids
(the two lowerings may differ in the last ulp; observed ~1e-16).
"""

import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.core import pareto, partition, stream, sweep
from repro.core.arrays import stacked_model_arrays
from repro.core.handtracking import build_detnet, build_keynet
from repro.core.workloads import NNWorkload
from repro.runtime import FaultInjector, FaultPlan, RetryPolicy

# The 10,880-config reference grid — keep in lockstep with
# benchmarks/sweep_bench.py::GRID (pinned here rather than imported so
# the test suite stays runnable without the benchmarks tree on sys.path).
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


# Chunk sizes: smaller than / close to / larger than the grid, and ones
# that do not divide 10,880 (997 is prime; 4096 leaves a remainder).
@pytest.fixture(scope="module", params=(997, 4096, 16384))
def streamed(request, dense):
    return stream.stream_grid(**REFERENCE_GRID, chunk_size=request.param,
                              top_k=TOP_K, track="all")


class TestStreamDenseParity:
    def test_grid_shape_matches(self, streamed, dense):
        assert streamed.shape == dense.shape
        assert streamed.n_configs == dense.n_configs == 10_880

    def test_argmin_exact_every_channel(self, streamed, dense):
        for field in sweep.FIELDS:
            assert streamed.argmin(field) == dense.argmin(field), field

    def test_top_k_exact(self, streamed, dense):
        for obj in streamed.objectives:
            assert streamed.top_k(obj) == dense.top_k(obj, TOP_K), obj

    def test_pareto_front_exact(self, streamed, dense_front):
        sf = streamed.pareto_front()
        assert np.array_equal(sf.indices, dense_front.indices)
        assert np.array_equal(sf.values, dense_front.values)

    def test_validity_counts_exact(self, streamed, dense):
        for field in sweep.FIELDS:
            expect = int(np.isfinite(dense.data[field]).sum())
            assert streamed.finite_counts[field] == expect, field
        # The grid mixes valid and invalid corners; both kinds exist.
        assert 0 < streamed.finite_counts["avg_power"] < streamed.n_configs

    def test_channel_bounds_exact(self, streamed, dense):
        for field in sweep.FIELDS:
            assert streamed.channel_bounds(field) == \
                dense.channel_bounds(field), field

    def test_hypervolume_matches_dense_default_ref(self, streamed,
                                                   dense_front):
        # channel_bounds parity makes even the default-reference
        # hypervolume identical across the two paths.
        assert streamed.pareto_front().hypervolume() == \
            pytest.approx(dense_front.hypervolume(), rel=1e-12)

    def test_config_at_roundtrip(self, streamed, dense):
        for flat in (0, 1234, streamed.n_configs - 1):
            assert streamed.config_at(flat) == dense.config_at(flat)


class TestStreamMechanics:
    def test_explicit_devices_pin_the_probe(self, monkeypatch):
        # An explicit devices= list must pin every device op of the run
        # (a CPU reference on a chip host evaluates nothing on the
        # chip), not only the chunk steps.
        import jax

        from repro.core import backend as B

        dev = jax.devices()[-1]
        seen = []
        real = B.cached_dense_eval

        def spy(*a, **kw):
            fn = real(*a, **kw)

            def run(axvals, flat):
                seen.append(flat.devices())
                return fn(axvals, flat)
            return run

        monkeypatch.setattr(B, "cached_dense_eval", spy)
        with jax.default_device(jax.devices()[0]):
            stream.stream_grid(cuts=(0, 17), detnet_fps=(5.0, 30.0),
                               devices=[dev])
        assert seen and all(s == {dev} for s in seen)

    def test_histograms_match_dense(self, dense):
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=777,
                                 hist_bins=16)
        for field in res.objectives:
            counts, edges = res.hist[field]
            vals = dense.data[field].ravel()
            vals = vals[np.isfinite(vals)]
            expect = np.histogram(np.clip(vals, edges[0], edges[-1]),
                                  bins=edges)[0]
            assert np.array_equal(counts, expect), field
            assert counts.sum() == vals.size

    def test_explicit_hist_ranges(self, dense):
        res = stream.stream_grid(cuts=(0, 17, 33), hist_bins=4,
                                 hist_ranges={"avg_power": (0.0, 1.0)})
        counts, edges = res.hist["avg_power"]
        assert edges[0] == 0.0 and edges[-1] == 1.0
        assert counts.sum() == 3

    def test_chunk_larger_than_grid(self, dense):
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=1 << 20)
        assert res.argmin() == dense.argmin()
        assert res.stats["n_chunks"] == 1

    def test_single_config_grid(self):
        res = stream.stream_grid(cuts=(17,))
        one = sweep.evaluate_one(17)
        assert res.n_configs == 1
        assert res.argmin()["avg_power"] == pytest.approx(one["avg_power"])

    def test_top_k_truncated_on_tiny_grids(self):
        res = stream.stream_grid(cuts=(0, 1, 2), top_k=8)
        got = res.top_k("avg_power")
        assert len(got) == 3          # fewer valid configs than k
        vals = [c["avg_power"] for c in got]
        assert vals == sorted(vals)

    def test_untracked_channel_is_informative(self):
        res = stream.stream_grid(cuts=(0, 1))
        with pytest.raises(ValueError, match="track"):
            res.argmin("camera")
        with pytest.raises(ValueError, match="objectives"):
            res.top_k("camera")

    def test_all_invalid_raises_naming_axes(self):
        res = stream.stream_grid(cuts=(1, 2), sensor_nodes=("7nm",),
                                 weight_mems=("mram",))
        with pytest.raises(ValueError, match="invalid") as ei:
            res.argmin()
        assert "mram" in str(ei.value)

    def test_maximize_objective(self, dense):
        res = stream.stream_grid(
            **REFERENCE_GRID, chunk_size=3333,
            objectives=("avg_power", "sensor_macs_per_s"),
            maximize=("sensor_macs_per_s",))
        macs = dense.data["sensor_macs_per_s"].ravel()
        best = res.top_k("sensor_macs_per_s")[0]
        assert best["sensor_macs_per_s"] == float(np.nanmax(macs))
        sf = res.pareto_front()
        assert np.isfinite(sf.values).all() and sf.size > 0

    def test_rejects_bad_parameters(self):
        with pytest.raises(ValueError, match="objective"):
            stream.stream_grid(cuts=(0,), objectives=())
        with pytest.raises(ValueError, match="unknown"):
            stream.stream_grid(cuts=(0,), objectives=("nope",))
        with pytest.raises(ValueError, match="maximize"):
            stream.stream_grid(cuts=(0,), maximize=("latency",),
                               objectives=("avg_power",))
        with pytest.raises(ValueError):
            stream.stream_grid(cuts=(99,))

    def test_memory_is_chunked_not_dense(self):
        """The streamed result retains O(front + k) state only — no
        channel array anywhere near the grid size."""
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=512)
        footprint = (res.front_values.size + res.front_indices.size
                     + res.topk_val.size + res.topk_idx.size)
        assert footprint < res.n_configs / 10
        assert not hasattr(res, "data")


class TestStackedWorkloads:
    @pytest.fixture(scope="class")
    def pairs(self):
        det, key = build_detnet(), build_keynet()
        short_key = NNWorkload(name="KeyNetShort", layers=key.layers[:4],
                               input_bytes=key.layers[0].in_act_bytes,
                               output_bytes=key.layers[3].out_act_bytes)
        return ((det, key), (det.scaled(0.5), key), (det, short_key))

    @pytest.fixture(scope="class")
    def stacked(self, pairs):
        return sweep.evaluate_grid(models=pairs, sensor_nodes=("7nm",
                                                               "16nm"),
                                   detnet_fps=(10.0, 30.0))

    def test_each_model_matches_its_single_grid(self, pairs, stacked):
        """Satellite requirement: stacked rows reproduce the single-model
        evaluate_grid to <=1e-6 (observed: bitwise on this lowering)."""
        for mi, (det, key) in enumerate(pairs):
            single = sweep.evaluate_grid(detnet=det, keynet=key,
                                         sensor_nodes=("7nm", "16nm"),
                                         detnet_fps=(10.0, 30.0))
            n_cuts = len(det.layers) + len(key.layers) + 1
            for field in sweep.FIELDS:
                a = stacked.data[field][mi, :n_cuts]
                b = single.data[field]
                both = np.isfinite(a) & np.isfinite(b)
                assert (np.isfinite(a) == np.isfinite(b)).all()
                denom = np.maximum(np.abs(b[both]), 1e-30)
                assert (np.abs(a[both] - b[both]) / denom <= 1e-6).all(), \
                    (field, mi)

    def test_padded_cuts_are_poisoned(self, pairs, stacked):
        """Cuts beyond a model's own range address padding and must NaN
        every channel (the docs/equations.md padded-cut mask)."""
        det, short_key = pairs[2]
        n_cuts = len(det.layers) + len(short_key.layers) + 1
        for field in sweep.FIELDS:
            assert np.isnan(stacked.data[field][2, n_cuts:]).all(), field

    def test_model_axis_in_result(self, stacked):
        assert list(stacked.axes)[0] == "model"
        assert stacked.axes["model"] == ("DetNet+KeyNet",
                                         "DetNetx0.5+KeyNet",
                                         "DetNet+KeyNetShort")
        best = stacked.argmin()
        assert best["model"] in stacked.axes["model"]

    def test_streamed_stack_matches_dense_stack(self, pairs, stacked):
        res = stream.stream_grid(models=pairs, sensor_nodes=("7nm", "16nm"),
                                 detnet_fps=(10.0, 30.0), chunk_size=97)
        for obj in res.objectives:
            d, s = stacked.argmin(obj), res.argmin(obj)
            assert {k: v for k, v in d.items() if k != obj} == \
                {k: v for k, v in s.items() if k != obj}
            assert s[obj] == pytest.approx(d[obj], rel=1e-12)
        assert res.finite_counts["avg_power"] == \
            int(np.isfinite(stacked.avg_power).sum())
        # The two lowerings of a *stacked* batch may differ in the last
        # ulp (single-model grids are pinned exactly in
        # TestStreamDenseParity), which can flip near-tie front
        # membership — compare fronts semantically instead: per-member
        # channel values and the dominated hypervolume.
        sf = res.pareto_front()
        df = pareto.pareto_front(stacked)
        for flat, vals in zip(sf.indices, sf.values):
            dvals = [float(stacked.data[o].ravel()[flat])
                     for o in res.objectives]
            np.testing.assert_allclose(vals, dvals, rtol=1e-9)
        ref = {o: stacked.channel_bounds(o)[1] * 1.01
               for o in res.objectives}
        assert sf.hypervolume(ref) == pytest.approx(df.hypervolume(ref),
                                                    rel=1e-6)

    def test_stacked_model_arrays_validation(self, pairs):
        S = stacked_model_arrays(pairs)
        assert S.n_models == 3
        assert S.n_cuts.tolist() == [34, 34, 23]   # det 18 + key 4 + 1
        assert S.n_cuts_max == 34
        with pytest.raises(ValueError):
            stacked_model_arrays(())

    def test_models_exclusive_with_single_model_args(self, pairs):
        with pytest.raises(ValueError, match="models"):
            sweep.evaluate_grid(models=pairs, detnet=build_detnet())


class TestShardedStream:
    def test_pmap_sharding_matches_dense(self):
        """Force 4 host devices in a subprocess and pin the pmap-sharded
        stream to the dense result (argmin + top-k + front, exact)."""
        code = """
import numpy as np
from repro.core import pareto, stream, sweep
GRID = dict(agg_nodes=("7nm","16nm"), sensor_nodes=("7nm","16nm"),
            weight_mems=("sram","mram"), detnet_fps=(5.,10.,30.))
dense = sweep.evaluate_grid(**GRID)
res = stream.stream_grid(**GRID, chunk_size=64)
assert res.n_devices == 4, res.n_devices
assert all(res.argmin(f) == dense.argmin(f) for f in res.objectives)
assert all(res.top_k(o) == dense.top_k(o, 4) for o in res.objectives)
df = pareto.pareto_front(dense); sf = res.pareto_front()
assert np.array_equal(df.indices, sf.indices)
assert np.array_equal(df.values, sf.values)
print("SHARDED-OK")
"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARDED-OK" in out.stdout


class TestOptimalPartitionRouting:
    def test_sequence_knobs_search_the_grid(self):
        best = partition.optimal_partition(sensor_node=("7nm", "16nm"),
                                           detnet_fps=(5.0, 10.0, 30.0))
        grid = sweep.evaluate_grid(sensor_nodes=("7nm", "16nm"),
                                   detnet_fps=(5.0, 10.0, 30.0))
        win = grid.argmin()
        assert best.cut == win["cut"]
        assert best.avg_power == pytest.approx(win["avg_power"], rel=1e-9)

    def test_huge_spaces_route_through_streamer(self, monkeypatch):
        monkeypatch.setattr(partition, "STREAM_THRESHOLD", 8)
        via_stream = partition.optimal_partition(
            sensor_node=("7nm", "16nm"), detnet_fps=(5.0, 10.0, 30.0))
        monkeypatch.setattr(partition, "STREAM_THRESHOLD", 1 << 20)
        via_dense = partition.optimal_partition(
            sensor_node=("7nm", "16nm"), detnet_fps=(5.0, 10.0, 30.0))
        assert via_stream.cut == via_dense.cut
        assert via_stream.avg_power == via_dense.avg_power

    def test_cuts_axis_and_latency_objective(self):
        best = partition.optimal_partition(objective="latency",
                                           cuts=(0, 17, 33),
                                           sensor_node=("7nm", "16nm"))
        assert best.cut in (0, 17, 33)

    def test_scalar_call_unchanged(self):
        a = partition.optimal_partition()
        b = partition.optimal_partition(sensor_node="7nm")
        assert a.cut == b.cut and a.avg_power == b.avg_power

    def test_sequences_reject_scalar_engine(self):
        with pytest.raises(ValueError, match="array"):
            partition.optimal_partition(engine="scalar",
                                        sensor_node=("7nm", "16nm"))

    def test_multi_knob_path_keeps_mram_vehicle_guard(self):
        """Opening a sequence knob must not bypass the scalar path's
        MRAM-vehicle rejection by quietly returning the one valid
        centralized point."""
        with pytest.raises(ValueError, match="MRAM"):
            partition.optimal_partition(sensor_weight_mem="mram",
                                        sensor_node="7nm",
                                        detnet_fps=(5.0, 10.0))
        # ... but a mixed axis with at least one valid combination is a
        # legitimate grid search.
        best = partition.optimal_partition(
            sensor_weight_mem=("sram", "mram"), sensor_node="7nm")
        assert best.cut >= 0

    def test_cuts_accepts_a_generator(self):
        best = partition.optimal_partition(cuts=(c for c in (0, 17, 33)))
        assert best.cut in (0, 17, 33)

    def test_evaluate_one_rejects_sequence_knobs(self):
        with pytest.raises(ValueError, match="scalar"):
            sweep.evaluate_one(17, detnet_fps=(5.0, 30.0))

    def test_unknown_knobs_raise_not_silently_drop(self):
        """A misspelled knob (e.g. the grid API's plural spelling) must
        not be swallowed by the multi-knob search path."""
        with pytest.raises(TypeError, match="sensor_nodes"):
            partition.optimal_partition(sensor_nodes=("7nm", "16nm"))
        with pytest.raises(TypeError, match="sensro_node"):
            partition.optimal_partition(sensro_node="7nm",
                                        detnet_fps=(5.0, 10.0))


class TestAsyncPipeline:
    """Satellite: the double-buffered pipeline must change nothing —
    exact argmin/top-k/front/count parity across prefetch depths
    {0, 1, 4} (0 = fully synchronous reference path) with a non-dividing
    chunk size."""

    @pytest.fixture(scope="class", params=(0, 1, 4))
    def piped(self, request):
        return stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                                  top_k=TOP_K, track="all",
                                  prefetch=request.param)

    def test_argmin_and_topk_exact(self, piped, dense):
        for field in sweep.FIELDS:
            assert piped.argmin(field) == dense.argmin(field), field
        for obj in piped.objectives:
            assert piped.top_k(obj) == dense.top_k(obj, TOP_K), obj

    def test_front_and_counts_exact(self, piped, dense, dense_front):
        sf = piped.pareto_front()
        assert np.array_equal(sf.indices, dense_front.indices)
        assert np.array_equal(sf.values, dense_front.values)
        for field in sweep.FIELDS:
            assert piped.finite_counts[field] == \
                int(np.isfinite(dense.data[field]).sum()), field

    def test_prefetch_recorded_in_stats(self, piped):
        assert piped.stats["prefetch"] in (0.0, 1.0, 4.0)
        assert "host_merge_s" in piped.stats
        assert "device_wait_s" in piped.stats

    def test_consumer_exception_reaps_producer(self, monkeypatch):
        """A host-merge failure must propagate promptly and must not
        leave the producer thread wedged in q.put."""
        import threading

        def boom(*a, **k):
            raise RuntimeError("merge exploded")

        monkeypatch.setattr(stream, "_merge_into_front", boom)
        with pytest.raises(RuntimeError, match="merge exploded"):
            stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                               prefetch=2)
        assert not [t for t in threading.enumerate()
                    if t.name == "stream-producer" and t.is_alive()]


class TestConstraints:
    """Satellite: device-masked constraint predicates must equal a host
    post-filter of the dense grid (``SweepResult.constrain``) exactly."""

    @pytest.fixture(scope="class")
    def budgets(self, dense):
        return {
            "latency":
                float(np.nanquantile(dense.data["latency"], 0.4)),
            "mipi_bytes_per_s":
                ("<=",
                 float(np.nanquantile(dense.data["mipi_bytes_per_s"],
                                      0.7))),
        }

    @pytest.fixture(scope="class")
    def constrained(self, budgets):
        return stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                                  constraints=budgets, prefetch=4)

    @pytest.fixture(scope="class")
    def dense_constrained(self, dense, budgets):
        return dense.constrain(budgets)

    def test_front_matches_host_postfilter(self, constrained,
                                           dense_constrained):
        df = pareto.pareto_front(dense_constrained)
        sf = constrained.pareto_front()
        assert np.array_equal(df.indices, sf.indices)
        assert np.array_equal(df.values, sf.values)

    def test_argmin_topk_bounds_feasible_only(self, constrained,
                                              dense_constrained):
        for obj in constrained.objectives:
            assert constrained.argmin(obj) == dense_constrained.argmin(obj)
            assert constrained.top_k(obj) == \
                dense_constrained.top_k(obj, 4), obj
            assert constrained.channel_bounds(obj) == \
                dense_constrained.channel_bounds(obj), obj

    def test_feasible_counts_exact(self, constrained, dense_constrained):
        for obj in constrained.objectives:
            expect = int(np.isfinite(dense_constrained.data[obj]).sum())
            assert constrained.finite_counts[obj] == expect, obj
        n = constrained.n_configs
        assert 0 < constrained.finite_counts["avg_power"] < n

    def test_constraint_channels_tracked_automatically(self):
        res = stream.stream_grid(cuts=(0, 17, 33),
                                 objectives=("avg_power",),
                                 constraints={"latency": 1.0})
        assert "latency" in res.min_val     # auto-tracked for the mask
        assert res.constraints == (("latency", "<=", 1.0),)

    def test_spec_forms_equivalent(self):
        a = sweep.parse_constraints({"latency": 1e-3})
        b = sweep.parse_constraints([("latency", "<=", 1e-3)])
        c = sweep.parse_constraints(["latency <= 1e-3"])
        assert a == b == c == (("latency", "<=", 0.001),)
        assert sweep.parse_constraints(None) == ()

    def test_rejects_bad_specs(self):
        with pytest.raises(ValueError, match="unknown constraint"):
            sweep.parse_constraints({"nope": 1.0})
        with pytest.raises(ValueError, match="op"):
            sweep.parse_constraints([("latency", "==", 1.0)])
        with pytest.raises(ValueError, match="parse"):
            sweep.parse_constraints(["latency ?? 3"])

    def test_all_infeasible_raises_naming_constraints(self):
        res = stream.stream_grid(cuts=(0, 1, 2),
                                 constraints={"latency": -1.0})
        with pytest.raises(ValueError, match="constraint"):
            res.argmin()
        with pytest.raises(ValueError, match="constraint"):
            res.channel_bounds("avg_power")

    def test_optimal_partition_constraint_plumbing(self, dense, budgets):
        best = partition.optimal_partition(
            sensor_node=("7nm", "16nm"),
            constraints={"latency": budgets["latency"]})
        grid = sweep.evaluate_grid(sensor_nodes=("7nm", "16nm"))
        win = grid.constrain({"latency": budgets["latency"]}).argmin()
        assert best.cut == win["cut"]
        assert best.latency <= budgets["latency"]

    def test_optimal_partition_infeasible_raises(self):
        with pytest.raises(ValueError, match="constraint"):
            partition.optimal_partition(constraints={"latency": -1.0})
        with pytest.raises(ValueError, match="constraint"):
            partition.optimal_partition(detnet_fps=(5.0, 10.0),
                                        constraints={"latency": -1.0})


class TestSurvivorOverflowFallback:
    def test_tiny_cap_forces_exact_host_fallback(self, dense_front,
                                                 monkeypatch):
        """A survivor-capacity overflow must fall back to an exact host
        re-derivation of the chunk, never silently truncate the front."""
        monkeypatch.setattr(stream, "_SURVIVOR_CAP", 8)
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=2048)
        assert res.stats["fallback_chunks"] > 0
        sf = res.pareto_front()
        assert np.array_equal(sf.indices, dense_front.indices)
        assert np.array_equal(sf.values, dense_front.values)


class TestDecodeHelper:
    def test_roundtrip_against_unravel_index(self):
        shape = (3, 5, 2, 7)
        flat = np.arange(np.prod(shape))
        ours = sweep.decode_flat_index(shape, flat)
        ref = np.unravel_index(flat, shape)
        for a, b in zip(ours, ref):
            assert np.array_equal(a, b)

    def test_scalar_decode(self):
        assert sweep.decode_flat_index((4, 6), 17) == (2, 5)

    def test_config_at_bounds(self, dense):
        with pytest.raises(IndexError):
            dense.config_at(dense.n_configs)


class TestMergeFronts:
    def test_merge_is_exact_and_order_independent(self):
        rng = np.random.default_rng(7)
        V = rng.random((300, 3))
        I = np.arange(300, dtype=np.int64)
        whole = pareto.non_dominated_mask(V)
        for cut_at in (1, 57, 150, 299):
            va, ia = pareto.merge_fronts(
                np.empty((0, 3)), np.empty(0, np.int64),
                V[:cut_at], I[:cut_at], None)
            vb, ib = pareto.merge_fronts(va, ia, V[cut_at:], I[cut_at:],
                                         None)
            assert set(ib.tolist()) == set(I[whole].tolist())

    def test_sign_orients_dominance(self):
        V = np.array([[1.0, 1.0], [2.0, 2.0]])
        _, idx_min = pareto.merge_fronts(np.empty((0, 2)),
                                         np.empty(0, np.int64),
                                         V, np.array([0, 1]), None)
        _, idx_max = pareto.merge_fronts(np.empty((0, 2)),
                                         np.empty(0, np.int64),
                                         V, np.array([0, 1]),
                                         np.array([-1.0, -1.0]))
        assert idx_min.tolist() == [0]
        assert idx_max.tolist() == [1]

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError):
            pareto.merge_fronts(np.empty((0, 2)), np.empty(0, np.int64),
                                np.ones((2, 2)), np.array([0]), None)


class _Abort(Exception):
    """Non-retryable sentinel: aborts a run without touching the retry
    or restart machinery (models an operator kill / preemption that the
    executor must *not* paper over in-process)."""


class _AbortAt:
    """Injector raising :class:`_Abort` once at a given chunk ordinal."""

    def __init__(self, ordinal: int):
        self.ordinal = ordinal
        self.fired = False

    def __call__(self, chunk_ordinal, flat_start):
        if not self.fired and chunk_ordinal >= self.ordinal:
            self.fired = True
            raise _Abort(f"aborted at chunk {chunk_ordinal}")


def _assert_full_parity(res, dense, dense_front):
    """Bitwise parity on every deliverable vs the dense reference."""
    for field in sweep.FIELDS:
        assert res.argmin(field) == dense.argmin(field), field
        assert res.finite_counts[field] == \
            int(np.isfinite(dense.data[field]).sum()), field
        assert res.channel_bounds(field) == \
            dense.channel_bounds(field), field
    for obj in res.objectives:
        assert res.top_k(obj) == dense.top_k(obj, TOP_K), obj
    sf = res.pareto_front()
    assert np.array_equal(sf.indices, dense_front.indices)
    assert np.array_equal(sf.values, dense_front.values)


class TestFaultToleranceAndResume:
    """Tentpole: checkpointed carries, retrying executor, deterministic
    fault injection.  Every recovery path must deliver *bitwise* the
    dense-path results — fault tolerance that changes answers is worse
    than none."""

    CKPT_KW = dict(chunk_size=997, top_k=TOP_K, track="all")

    def test_transient_faults_retry_to_exact_parity(self, dense,
                                                    dense_front):
        """raise-on-chunk-k plus seeded transient errors: bounded
        in-place retries must converge with untouched results."""
        inj = FaultInjector(FaultPlan(fail_chunks=(2,),
                                      transient_rate=0.2, seed=7))
        res = stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                                 fault_injector=inj)
        assert inj.injected["transient"] >= 1
        assert res.stats["retries"] == inj.injected["transient"]
        _assert_full_parity(res, dense, dense_front)

    def test_retries_exhausted_raises(self):
        """A chunk that keeps failing must surface the fault, not spin."""

        class _AlwaysFail:
            def __call__(self, chunk_ordinal, flat_start):
                from repro.runtime import TransientDeviceError
                raise TransientDeviceError("permanent (injected)")

        policy = RetryPolicy(max_retries=1, max_restarts=1,
                             backoff_s=0.0)
        from repro.runtime import TransientDeviceError
        with pytest.raises(TransientDeviceError):
            stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                               retry_policy=policy,
                               fault_injector=_AlwaysFail())

    def test_abort_resume_bitwise_parity(self, dense, dense_front,
                                         tmp_path):
        """Kill at an arbitrary chunk boundary; the resumed run must
        pick up from the checkpoint cursor and deliver bitwise-identical
        results."""
        ckpt = str(tmp_path / "ckpt")
        with pytest.raises(_Abort):
            stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                               checkpoint_dir=ckpt,
                               checkpoint_every_steps=1,
                               fault_injector=_AbortAt(3))
        res = stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                                 checkpoint_dir=ckpt,
                                 checkpoint_every_steps=1)
        assert res.stats["resumed_from_step"] > 0
        _assert_full_parity(res, dense, dense_front)

    def test_resume_mid_scan_chunks_macro_step(self, dense, dense_front,
                                               tmp_path):
        """With scan fusion one macro step covers several chunks; the
        checkpoint cursor must land on macro-step boundaries and resume
        exactly."""
        ckpt = str(tmp_path / "ckpt")
        kw = dict(chunk_size=997, scan_chunks=4, top_k=TOP_K,
                  track="all")
        with pytest.raises(_Abort):
            stream.stream_grid(**REFERENCE_GRID, **kw,
                               checkpoint_dir=ckpt,
                               checkpoint_every_steps=1,
                               fault_injector=_AbortAt(8))
        res = stream.stream_grid(**REFERENCE_GRID, **kw,
                                 checkpoint_dir=ckpt,
                                 checkpoint_every_steps=1)
        assert res.stats["resumed_from_step"] > 0
        assert res.stats["resumed_from_step"] % 4 == 0
        _assert_full_parity(res, dense, dense_front)

    def test_resume_from_completed_run(self, dense, dense_front,
                                       tmp_path):
        """The terminal snapshot makes a finished sweep re-runnable
        without recomputation and without corrupting the answers."""
        ckpt = str(tmp_path / "ckpt")
        stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                           checkpoint_dir=ckpt)
        res = stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                                 checkpoint_dir=ckpt)
        assert res.stats["resumed_from_step"] > 0
        _assert_full_parity(res, dense, dense_front)

    def test_stale_signature_rejected_loudly(self, tmp_path):
        """A checkpoint from a different sweep spec must fail with a
        clear error, never silently merge."""
        ckpt = str(tmp_path / "ckpt")
        stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                           checkpoint_dir=ckpt)
        with pytest.raises(ValueError, match="different sweep job"):
            stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                               top_k=TOP_K + 1, track="all",
                               checkpoint_dir=ckpt)

    def test_straggler_detector_flags_injected_delay(self, dense):
        """An injected dispatch delay past the warmup window must be
        counted (trigger ordinal is after the detector's 3-sample
        warmup)."""
        inj = FaultInjector(FaultPlan(straggle={24: 1.0}))
        policy = RetryPolicy(straggler_factor=4.0, straggler_window=32)
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=256,
                                 retry_policy=policy, fault_injector=inj)
        assert inj.injected["straggle"] == 1
        assert res.stats["stragglers"] >= 1
        assert res.argmin() == dense.argmin()

    def test_stats_expose_resilience_counters(self, dense):
        res = stream.stream_grid(**REFERENCE_GRID, chunk_size=997)
        # Deterministically zero on a fault-free run without checkpoints.
        for key in ("retries", "restarts", "resumed_from_step",
                    "checkpoint_write_s", "checkpoints_written",
                    "chunks_reissued", "elastic_replans"):
            assert res.stats[key] == 0.0, key
        # Load-dependent observations: a busy CI host can legitimately
        # produce slow dispatches, so only presence is pinned.
        for key in ("stragglers", "step_timeouts"):
            assert res.stats[key] >= 0.0, key

    def test_checkpoint_counters_in_stats(self, tmp_path):
        res = stream.stream_grid(**REFERENCE_GRID, **self.CKPT_KW,
                                 checkpoint_dir=str(tmp_path / "c"),
                                 checkpoint_every_steps=2)
        assert res.stats["checkpoints_written"] >= 2
        assert res.stats["checkpoint_write_s"] > 0.0

    def test_optimal_partition_checkpoint_plumbing(self, monkeypatch,
                                                   tmp_path):
        """``optimal_partition(checkpoint_dir=...)`` must reach the
        streaming route and leave durable checkpoints behind."""
        monkeypatch.setattr(partition, "STREAM_THRESHOLD", 8)
        ckpt = str(tmp_path / "ckpt")
        best = partition.optimal_partition(
            sensor_node=("7nm", "16nm"), detnet_fps=(5.0, 10.0, 30.0),
            checkpoint_dir=ckpt, checkpoint_every_s=0.0)
        assert os.path.isdir(ckpt) and os.listdir(ckpt)
        monkeypatch.setattr(partition, "STREAM_THRESHOLD", 1 << 20)
        ref = partition.optimal_partition(
            sensor_node=("7nm", "16nm"), detnet_fps=(5.0, 10.0, 30.0))
        assert best.cut == ref.cut
        assert best.avg_power == ref.avg_power


class TestShardedFaultTolerance:
    """Recovery under pmap sharding: elastic replan on device loss, and
    SIGKILL kill-resume parity (each in a 4-host-device subprocess)."""

    @staticmethod
    def _run(code: str) -> subprocess.CompletedProcess:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        return subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=600)

    def test_device_loss_triggers_elastic_replan(self):
        code = """
import numpy as np
from repro.core import pareto, stream, sweep
from repro.runtime import FaultInjector, FaultPlan
GRID = dict(agg_nodes=("7nm","16nm"), sensor_nodes=("7nm","16nm"),
            weight_mems=("sram","mram"), detnet_fps=(5.,10.,15.,20.,30.),
            keynet_fps=(15.,30.), num_cameras=(2,4),
            mipi_energy_scale=(1.,2.))
dense = sweep.evaluate_grid(**GRID)
inj = FaultInjector(FaultPlan(lose_device=(5, 2)))
res = stream.stream_grid(**GRID, chunk_size=256, top_k=4, track="all",
                         fault_injector=inj)
assert res.n_devices == 4, res.n_devices
assert inj.injected["device_lost"] == 1
assert res.stats["elastic_replans"] == 1.0, res.stats
assert res.stats["chunks_reissued"] > 0.0, res.stats
assert all(res.argmin(f) == dense.argmin(f) for f in res.objectives)
assert all(res.top_k(o) == dense.top_k(o, 4) for o in res.objectives)
df = pareto.pareto_front(dense); sf = res.pareto_front()
assert np.array_equal(df.indices, sf.indices)
assert np.array_equal(df.values, sf.values)
print("ELASTIC-OK")
"""
        out = self._run(code)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "ELASTIC-OK" in out.stdout

    def test_sigkill_resume_bitwise_parity(self, tmp_path):
        """SIGKILL a sharded sweep mid-flight; a fresh process must
        resume from the durable snapshot and match the dense path
        bitwise."""
        ckpt = str(tmp_path / "ckpt")
        common = f"""
import numpy as np
from repro.core import pareto, stream, sweep
GRID = dict(agg_nodes=("7nm","16nm"), sensor_nodes=("7nm","16nm"),
            weight_mems=("sram","mram"), detnet_fps=(5.,10.,15.,20.,30.),
            keynet_fps=(15.,30.), num_cameras=(2,4),
            mipi_energy_scale=(1.,2.))
KW = dict(chunk_size=256, top_k=4, track="all",
          checkpoint_dir={ckpt!r}, checkpoint_every_steps=1)
"""
        kill = common + """
from repro.runtime import FaultInjector, FaultPlan
inj = FaultInjector(FaultPlan(kill_at=24))
stream.stream_grid(**GRID, **KW, fault_injector=inj)
print("UNREACHABLE")
"""
        resume = common + """
dense = sweep.evaluate_grid(**GRID)
res = stream.stream_grid(**GRID, **KW)
assert res.n_devices == 4, res.n_devices
assert res.stats["resumed_from_step"] > 0, res.stats
assert all(res.argmin(f) == dense.argmin(f) for f in res.objectives)
assert all(res.top_k(o) == dense.top_k(o, 4) for o in res.objectives)
df = pareto.pareto_front(dense); sf = res.pareto_front()
assert np.array_equal(df.indices, sf.indices)
assert np.array_equal(df.values, sf.values)
print("RESUME-OK", res.stats["resumed_from_step"])
"""
        out1 = self._run(kill)
        assert out1.returncode == -signal.SIGKILL, \
            (out1.returncode, out1.stderr[-2000:])
        assert "UNREACHABLE" not in out1.stdout
        out2 = self._run(resume)
        assert out2.returncode == 0, out2.stderr[-2000:]
        assert "RESUME-OK" in out2.stdout


class _StopAfter:
    """``should_stop`` hook returning True after ``n`` dispatches."""

    def __init__(self, n: int):
        self.n = n
        self.calls = 0

    def __call__(self) -> bool:
        self.calls += 1
        return self.calls > self.n


class TestCooperativeStop:
    """Cooperative cancellation: ``should_stop`` is polled between chunk
    dispatches; a halted run returns a *consistent prefix snapshot*
    (``partial=True``) — the exact reductions over flat configs
    ``[0, base)`` — never an error and never a torn mix of chunks."""

    KW = dict(chunk_size=997, top_k=TOP_K, track="all")

    @pytest.mark.parametrize("prefetch", (0, 2))
    def test_partial_snapshot_is_exact_prefix(self, dense, prefetch):
        res = stream.stream_grid(**REFERENCE_GRID, **self.KW,
                                 prefetch=prefetch,
                                 should_stop=_StopAfter(3))
        assert res.partial
        frac = res.stats["fraction_complete"]
        assert 0.0 < frac < 1.0
        base = round(frac * dense.data["avg_power"].size)
        assert base == 3 * 997      # stopped before the 4th dispatch
        for field in sweep.FIELDS:
            prefix = np.asarray(dense.data[field]).ravel()[:base]
            assert res.min_val[field] == float(np.nanmin(prefix)), field
            assert res.min_idx[field] == int(np.nanargmin(prefix)), field
            assert res.finite_counts[field] == \
                int(np.isfinite(prefix).sum()), field
            assert res.channel_min[field] == float(np.nanmin(prefix))
            assert res.channel_max[field] == float(np.nanmax(prefix))

    def test_never_stopping_hook_is_a_noop(self, dense, dense_front):
        res = stream.stream_grid(**REFERENCE_GRID, **self.KW,
                                 should_stop=lambda: False)
        assert not res.partial
        assert res.stats["fraction_complete"] == 1.0
        _assert_full_parity(res, dense, dense_front)

    def test_on_progress_monotonic_to_one(self):
        seen = []
        res = stream.stream_grid(**REFERENCE_GRID, **self.KW,
                                 on_progress=seen.append)
        assert seen == sorted(seen)
        assert seen[-1] == 1.0
        assert len(seen) == res.stats["n_chunks"]

    def test_partial_checkpoint_then_resume_completes(self, dense,
                                                      dense_front,
                                                      tmp_path):
        """A halted run leaves a durable snapshot at its stop cursor; a
        later run over the same checkpoint dir finishes the sweep
        bitwise-exactly."""
        ckpt = str(tmp_path / "ckpt")
        part = stream.stream_grid(**REFERENCE_GRID, **self.KW,
                                  checkpoint_dir=ckpt,
                                  checkpoint_every_steps=1,
                                  should_stop=_StopAfter(3))
        assert part.partial
        res = stream.stream_grid(**REFERENCE_GRID, **self.KW,
                                 checkpoint_dir=ckpt,
                                 checkpoint_every_steps=1)
        assert not res.partial
        assert res.stats["resumed_from_step"] == 3
        _assert_full_parity(res, dense, dense_front)

    def test_keyboard_interrupt_reaps_producer(self, monkeypatch):
        """Ctrl-C in the consumer loop must still signal and join the
        producer thread — the satellite fix for the orphaned
        ``stream-producer`` after KeyboardInterrupt."""
        import threading

        def boom(*a, **k):
            raise KeyboardInterrupt

        monkeypatch.setattr(stream, "_merge_into_front", boom)
        with pytest.raises(KeyboardInterrupt):
            stream.stream_grid(**REFERENCE_GRID, chunk_size=997,
                               prefetch=2)
        assert not [t for t in threading.enumerate()
                    if t.name == "stream-producer" and t.is_alive()]


class TestPlanReuse:
    """``plan_stream`` + ``stream_grid(plan=)``: the resolved plan is
    the service's cache currency — running through a pre-resolved plan
    must be bitwise-identical to the keyword path, and the content
    signature must be stable across resolutions."""

    def test_plan_path_bitwise_equals_keyword_path(self, dense,
                                                   dense_front):
        plan = stream.plan_stream(**REFERENCE_GRID, chunk_size=997,
                                  top_k=TOP_K, track="all")
        res = stream.stream_grid(plan=plan)
        _assert_full_parity(res, dense, dense_front)

    def test_signature_stable_across_resolutions(self):
        kw = dict(chunk_size=997, top_k=TOP_K, track="all")
        p1 = stream.plan_stream(**REFERENCE_GRID, **kw)
        p2 = stream.plan_stream(**REFERENCE_GRID, **kw)
        assert p1.signature == p2.signature
        p3 = stream.plan_stream(**REFERENCE_GRID, chunk_size=997,
                                top_k=TOP_K + 1, track="all")
        assert p3.signature != p1.signature
