"""Trace reduction on a synthetic trace, and on one recorded here."""

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace as T  # noqa: E402

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def synthetic():
    """A 100 ms window: device 0 busy 10-30 and 50-60 ms, device 1 busy
    0-100 ms; ops overlap within device 0; host spans around them."""
    return [
        ev(HOST, "python", "bench.window", 0, 100),
        ev(HOST, "python", "bench.study", 5, 60),
        ev(HOST, "python", "bench.submit", 30, 20),
        ev(HOST, "python", "other.span", 0, 100),
        ev(DEV0, T.MODULE_LINE, "jit_step(12)", 10, 20),
        ev(DEV0, T.OP_LINE, "fusion.1", 10, 15),
        ev(DEV0, T.OP_LINE, "fusion.2", 20, 10),
        ev(DEV0, T.MODULE_LINE, "jit_evalfn(3)", 50, 10),
        ev(DEV0, T.OP_LINE, "fusion.3", 50, 10),
        ev(DEV0, T.OP_LINE, "late.op", 95, 20),      # clipped at 100
        ev(DEV1, T.OP_LINE, "fusion.1", 0, 100),
        ev(DEV1, T.MODULE_LINE, "pmap_step(4)", 0, 100),
    ]


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    r = T.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    d0 = r["devices"][DEV0]
    # union of [10,30] [50,60] [95,100] = 35 ms
    assert d0["busy_s"] == pytest.approx(0.035)
    assert d0["idle_share"] == pytest.approx(0.65)
    assert r["devices"][DEV1]["idle_share"] == pytest.approx(0.0)
    assert r["idle_share"] == pytest.approx(0.325)
    assert r["busy_s"] == pytest.approx((0.035 + 0.1) / 2)


def test_a_silent_device_of_the_cell_counts_as_idle():
    """A chip the cell uses but leaves idle has busy 0 and raises the
    mean idle share; a device outside the cell is not read."""
    dev2, dev9 = "/device:TPU:2", "/device:TPU:9"
    events = synthetic() + [ev(dev9, T.OP_LINE, "fusion.9", 0, 100)]
    r = T.reduce(events, planes=[DEV0, DEV1, dev2])
    assert set(r["devices"]) == {DEV0, DEV1, dev2}
    assert r["devices"][dev2] == {"busy_s": 0.0, "idle_share": 1.0}
    assert r["idle_share"] == pytest.approx((0.65 + 0.0 + 1.0) / 3)
    assert r["busy_s"] == pytest.approx((0.035 + 0.1) / 3)
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(0.115)
    assert T.reduce(events, planes=[dev2]) is None


def test_plane_names_follow_the_jax_device():
    class Dev:
        platform, id = "tpu", 3
    assert T.plane_name(Dev()) == "/device:TPU:3"


def test_program_time_drops_the_run_id_and_sums_over_devices():
    r = T.reduce(synthetic())
    assert r["program_s"]["jit_step"] == pytest.approx(0.02)
    assert r["program_s"]["jit_evalfn"] == pytest.approx(0.01)
    assert r["program_s"]["pmap_step"] == pytest.approx(0.1)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.115)
    assert ops["program pmap_step"] == pytest.approx(0.1)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    r = T.reduce(synthetic())
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx(
        sorted([g[1] for g in gaps], reverse=True))
    # device 0's gaps: [0,10] [30,50] [60,95]
    named = {round(s * 1e3): n for n, s in gaps}
    assert named[35] == "other.span"          # midpoint 77.5 ms
    assert named[20] == "bench.submit"        # midpoint 40 ms
    assert named[10] == "bench.study"         # midpoint 5 ms
    assert math.isclose(sum(s for _, s in gaps), 0.065)


def test_no_device_events_reads_nothing():
    assert T.reduce([ev(HOST, "python", "bench.window", 0, 10)]) is None
    assert T.reduce([]) is None


def test_a_trace_recorded_on_the_cpu_loads(tmp_path):
    """The loader reads a real profiler file: the benchmark's host spans
    come through, and a CPU trace has no device plane to reduce."""
    jax = pytest.importorskip("jax")
    f = jax.jit(lambda a: (a @ a).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = T.load(str(tmp_path))
    assert any(e[2] == "bench.window" and e[0].startswith("/host:")
               for e in events)
    assert T.reduce(events) is None
