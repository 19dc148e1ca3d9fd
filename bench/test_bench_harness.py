"""CPU tests of the benchmark harness; no chip is claimed."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]

import reference  # noqa: E402
import run  # noqa: E402
import stats  # noqa: E402
import tiny  # noqa: E402
import traffic as traffic_mod  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]]
SEEDS = (0, 17, 2**31 + 11)


@pytest.mark.parametrize("cell", CELLS)
def test_every_draw_of_a_cell_has_one_chunk_spec(cell):
    """Seeds change values, never shapes: every study or query a cell
    draws resolves to the same compiled step and the same probe."""
    import jax
    from repro.core import stream

    _, w, config, traffic = run.load_cell(REPO, cell)
    devs = jax.devices("cpu")[:1] * int(w["chips"])
    specs = set()
    for seed in SEEDS:
        if traffic["kind"] == "closed_loop":
            gen = traffic_mod.studies(config, seed)
            draws = [next(gen) for _ in range(3)]
        else:
            draws = [q[1:3] for q in
                     traffic_mod.queries(config, traffic, seed, 51)[:20]]
        for study, budgets in draws:
            plan = stream.plan_stream(
                **traffic_mod.stream_kwargs(study),
                constraints=budgets or None,
                objectives=tuple(config["objectives"]),
                top_k=config["top_k"], devices=devs)
            specs.add((plan.spec, plan.scan, plan.n_total))
    assert len(specs) == 1


def test_open_loop_schedule_is_the_same_work_in_another_order():
    _, _, config, traffic = run.load_cell(REPO,
                                          "ht-partition-1e7.interactive")
    a = traffic_mod.queries(config, traffic, 1, 51)
    b = traffic_mod.queries(config, traffic, 2**31 + 3, 51)
    gaps = [sorted(q[0] for q in s) for s in (a, b)]
    assert len(a) == len(b) == int(traffic["rate_per_s"] * 51)
    assert gaps[0][-1] < 51 and gaps[1][-1] < 51
    da = sorted(x - y for x, y in zip(gaps[0][1:], gaps[0]))
    db = sorted(x - y for x, y in zip(gaps[1][1:], gaps[1]))
    assert sum(da) == pytest.approx(sum(db), rel=0.05)
    assert traffic_mod.queries(config, traffic, 1, 51)[5][1] == a[5][1]
    assert len({json.dumps(q[1:3]) for q in a}) == len(a)


def test_whole_window_rate_is_all_work_over_all_time():
    studies = [{"n_configs": 100, "t_start": 10.0, "t_end": 12.0},
               {"n_configs": 100, "t_start": 12.0, "t_end": 13.0},
               {"n_configs": 200, "t_start": 13.5, "t_end": 18.0}]
    assert stats.whole_window_rate(studies, 10.0) == pytest.approx(50.0)
    assert math.isnan(stats.whole_window_rate([], 0.0))


def test_percentiles_are_nearest_rank_and_failures_rank_last():
    xs = [float(i) for i in range(1, 101)]
    assert stats.percentile(xs, 95) == 95.0
    assert stats.percentile(xs, 50) == 50.0
    assert stats.percentile(xs[:20], 95) == 19.0
    with_fail = xs[:18] + [None, None]
    assert stats.percentile(with_fail, 95) == math.inf
    assert stats.percentile(with_fail, 50) == 10.0


def _bench_cmd(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "ht-partition-1e7.batch", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_that_finds_no_tpu_fails_and_prints_no_result():
    p = _bench_cmd(REPO)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_a_directory_without_the_program_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


REFUSE = """
import sys
sys.path.insert(0, sys.argv[1])
import run
from pathlib import Path
rc = run.main(["--workload", "tiny-typo.batch", "--seed", "1", "--seconds",
               "1"], require_chip=False, root=Path(sys.argv[2]))
print("RC", rc, "JAX", "jax" in sys.modules)
"""


def test_a_channel_the_reference_lacks_is_refused_before_jax(tmp_path):
    """A configuration that names a channel the plain reference cannot
    check would fail only after its window: it is refused with exit
    code 2 and the channel's name, before JAX is imported."""
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = dict(tiny.TINY, name="tiny-typo",
                  objectives=["avg_power", "sensor_mac_per_s"],
                  constraints={"lattency": {"uniform": [0.014, 0.016]}})
    (root / "bench" / "configs" / "tiny-typo.json").write_text(
        json.dumps(config))
    bench["configs"].append({"name": "tiny-typo", "source": "CPU tests",
                             "file": "bench/configs/tiny-typo.json",
                             "reduced": [], "why": "CPU tests"})
    bench["workloads"].append({"name": "tiny-typo.batch",
                               "config": "tiny-typo", "traffic": "batch",
                               "chips": 1, "why": "CPU tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", REFUSE, str(BENCH),
                        str(root)], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == "RC 2 JAX False"
    assert "sensor_mac_per_s" in p.stderr and "lattency" in p.stderr
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "tiny-typo.batch", "--seed", "1", "--seconds", "1"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "sensor_mac_per_s" in p.stderr


def test_the_plain_reference_agrees_with_the_engine():
    """At a small size the float64 reference and the engine give the
    same configurations, counts and front."""
    from repro.core import stream

    study = traffic_mod.base_study(tiny.TINY)
    budgets = {"latency": 15e-3, "mipi_bytes_per_s": 25e6}
    objectives = tuple(tiny.TINY["objectives"])
    res = stream.stream_grid(**traffic_mod.stream_kwargs(study),
                             constraints=budgets, objectives=objectives)
    ref = reference.solve(study, budgets, objectives, 4)
    assert res.finite_counts == ref["finite_counts"]
    assert res.min_idx == ref["min_idx"]
    assert (res.topk_idx == ref["topk_idx"]).all()
    assert (res.front_indices == ref["front_indices"]).all()
    assert abs(res.front_values / ref["front_values"] - 1).max() < 1e-13


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,trace", [("tiny.batch", 0), ("tiny.batch", 1),
                                        ("tiny.open", 0), ("tiny.open", 1)])
def test_a_cell_added_as_files_and_entries_alone_runs(root, cell, trace):
    """A new configuration, traffic mix and cells, and a new per-layer
    metric, found by name with no edit to the harness."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if not any(m["name"] == "studies_done" for m in bench["per_layer"]):
        (root / "bench" / "metrics" / "studies_done.py").write_text(
            "def read(run):\n    return len(run['studies']) or None\n")
        bench["per_layer"].append({
            "name": "studies_done", "unit": "studies", "better": "higher",
            "source": "host_clock", "layer": "executor",
            "moves": "configs_per_s"})
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out, lines = tiny.run_cell(root, cell, trace=trace)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    window = next(x["window"] for x in lines if "window" in x)
    assert window["compiles_in_window"] == 0
    if trace:
        assert "setup_s" not in out["metrics"]
        assert ("studies_done" in out["metrics"]) == (cell == "tiny.batch")
        assert ("ticket_p95_s" in out["metrics"]) == (cell == "tiny.open")
    else:
        assert set(out["metrics"]) == ({"configs_per_s", "setup_s"}
                                       if cell == "tiny.batch" else
                                       {"ticket_p50_s", "setup_s"})


def test_the_stall_watch_records_late_wakes_and_collector_pauses():
    """With thresholds at or below zero every wake and every collection
    counts, so the watch's plumbing shows on any machine."""
    import gc
    import time

    watch = run.StallWatch(tick=0.005, late=-1.0)
    watch.start(time.perf_counter())
    time.sleep(0.05)
    gc.collect()
    out = watch.stop()
    assert out["late_wakes"] >= 3
    assert len(out["worst"]) <= 8 and out["worst"][0][1] >= -1.0
    assert out["gc_pauses"] and out["gc_pauses"][0][2] == 2
    assert all(getattr(cb, "__self__", None) is not watch
               for cb in gc.callbacks)
    quiet = run.StallWatch(tick=0.005, late=5.0)
    quiet.start(time.perf_counter())
    time.sleep(0.02)
    assert quiet.stop()["late_wakes"] == 0
