"""Streaming vs dense design-space sweeps: throughput and peak memory.

    PYTHONPATH=src python -m benchmarks.stream_bench

Measures the streaming executor (`repro.core.stream.stream_grid`) against
the dense grid engine (`repro.core.sweep.evaluate_grid`) at 10^5 / 10^6 /
10^7 configurations.  Both modes are timed to the *same deliverables* —
per-objective argmin, top-k, channel bounds, feasibility counts, and the
exact Pareto front (everything a `StreamResult` always carries; the
dense worker runs the equivalent `SweepResult`/`pareto` calls) — so
`configs_per_s` compares completing the same sweep analysis.  The dense
worker additionally reports `eval_configs_per_s` (evaluation only, the
PR-1/PR-3 comparable number).  The stream worker runs sharded across
CPU cores (its deployment configuration); each measurement runs in its
own subprocess so peak RSS is attributable per (mode, size) — dense
memory grows O(grid) (unrunnable at 10^7 on small hosts) while
streaming stays flat at O(chunk + front).  Exact argmin/top-k/
Pareto-front parity on the 10,880-config reference grid is asserted and
recorded.

Scan-fused dispatch (``stream_grid(scan_chunks=)``, the backend layer's
``lax.scan`` over K chunk carries per device dispatch) is measured at
10^7 and a streaming-only 10^8-config point: per-chunk ``dispatch_s``
and ``steps_per_s`` are recorded alongside the merge-stall fields, with
the forced ``scan_chunks=1`` per-chunk baseline for the overhead ratio.

Checkpoint overhead (``stream_grid(checkpoint_dir=)``, the fault-
tolerance tentpole's durable snapshots) is measured at 10^7 against the
bare streaming run: the default 30 s interval (target < 2% throughput
loss) and a 1 s worst case, each into a fresh directory per repetition
so nothing resumes.  Emits ``name,value,derived`` rows and snapshots
``BENCH_stream.json`` at the repo root.
"""

from __future__ import annotations

import json
import os
import pathlib
import resource
import subprocess
import sys
import time

import numpy as np

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_stream.json"
SRC = pathlib.Path(__file__).resolve().parent.parent / "src"

#: The PR-1 reference grid (10,880 configs) — the exact-parity anchor,
#: shared with the dense-engine benchmark so the two suites can never
#: drift onto different grids.
from benchmarks.sweep_bench import GRID as REFERENCE_GRID  # noqa: E402


def _grid_for(n: int) -> dict:
    """Reference grid widened along the rate axes to ~n configurations."""
    g = dict(REFERENCE_GRID)
    if n >= 100_000_000:
        g["detnet_fps"] = tuple(np.linspace(5.0, 30.0, 50))
        g["keynet_fps"] = tuple(np.linspace(15.0, 30.0, 20))
        g["camera_fps"] = tuple(np.linspace(20.0, 60.0, 92))   # 100,096,000
    elif n >= 10_000_000:
        g["detnet_fps"] = tuple(np.linspace(5.0, 30.0, 50))
        g["camera_fps"] = tuple(np.linspace(20.0, 60.0, 92))   # 10,009,600
    elif n >= 1_000_000:
        g["camera_fps"] = tuple(np.linspace(20.0, 60.0, 92))   # 1,000,960
    elif n >= 100_000:
        g["camera_fps"] = tuple(np.linspace(20.0, 60.0, 9))    # 97,920
    return g


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _mem_available_mb() -> float:
    try:
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemAvailable"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    return float("inf")


def _worker(mode: str, n: int, scan: int | None = None,
            ckpt_every_s: float | None = None) -> dict:
    from repro.core import stream, sweep

    grid = _grid_for(n)
    # Short runs are scheduler/frequency-noise dominated on small hosts:
    # take the best of more repetitions there (runs at these sizes are
    # tens of ms, so the extra reps are free next to the jit compile).
    reps = 8 if n <= 1_000_000 else (3 if n <= 10_000_000 else 1)
    if mode == "dense":
        import numpy as np

        from repro.core import pareto

        # 11 host channel grids + their device twins, minus what XLA
        # frees early — the meshgrid coordinate arrays are gone (the
        # dense engine decodes flat indices on device now), but the
        # gathered per-lane kernel inputs still exist transiently.
        need_mb = n * 8 * 21 / 2**20 * 1.5
        if need_mb > _mem_available_mb():
            return {"mode": mode, "n": n, "skipped":
                    f"needs ~{need_mb:.0f} MB dense grid memory, "
                    f"{_mem_available_mb():.0f} MB available"}
        res = sweep.evaluate_grid(**grid)          # compile + first run
        best = None
        for _ in range(reps):                      # post-compile, best-of
            t0 = time.perf_counter()
            res = sweep.evaluate_grid(**grid)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        # The headline configs_per_s covers the *same deliverables* a
        # StreamResult always carries — per-objective argmin, top-k,
        # bounds, feasibility counts, and the exact Pareto front — so
        # the two modes are compared on completing the same sweep
        # analysis.  eval_configs_per_s keeps the PR-1/PR-3 comparable
        # evaluation-only number in the trail.
        t0 = time.perf_counter()
        for o in pareto.DEFAULT_OBJECTIVES:
            res.argmin(o)
            res.top_k(o, 4)
            res.channel_bounds(o)
            int(np.isfinite(res.data[o]).sum())
        front = pareto.pareto_front(res)
        t_analysis = time.perf_counter() - t0
        return {"mode": mode, "n": res.n_configs,
                "configs_per_s": round(res.n_configs
                                       / (best + t_analysis), 1),
                "eval_configs_per_s": round(res.n_configs / best, 1),
                "analysis_s": round(t_analysis, 4),
                "front_size": int(front.size),
                "peak_rss_mb": round(_rss_mb(), 1),
                "best_power_mw": round(res.argmin()["avg_power"] * 1e3, 4)}
    kw = dict(grid)
    if scan is not None:
        kw["scan_chunks"] = scan
    res = stream.stream_grid(**kw)                 # compile + first run
    best_stats = None
    for _ in range(reps):                          # post-compile, best-of
        if ckpt_every_s is not None:
            # Fresh directory per repetition: a reused one would resume
            # from its own terminal snapshot and measure nothing.
            import shutil
            import tempfile
            ckpt_dir = tempfile.mkdtemp(prefix="stream_bench_ckpt_")
            try:
                res = stream.stream_grid(
                    **kw, checkpoint_dir=ckpt_dir,
                    checkpoint_every_s=ckpt_every_s)
            finally:
                shutil.rmtree(ckpt_dir, ignore_errors=True)
        else:
            res = stream.stream_grid(**kw)
        if (best_stats is None
                or res.stats["total_s"] < best_stats["total_s"]):
            best_stats = res.stats
    return {"mode": mode, "n": res.n_configs,
            "checkpoints_written":
                int(best_stats.get("checkpoints_written", 0)),
            "checkpoint_write_s":
                round(best_stats.get("checkpoint_write_s", 0.0), 4),
            "configs_per_s": round(res.n_configs
                                   / best_stats["total_s"], 1),
            "steady_configs_per_s":
                round(best_stats["steady_configs_per_s"], 1),
            "peak_rss_mb": round(_rss_mb(), 1),
            "front_size": int(res.front_indices.size),
            # Pipeline accounting: host-merge seconds (exact front/merge
            # work on the host) vs the time the host spent stalled on
            # device results — the overlap the async pipeline buys.
            "host_merge_s": round(best_stats["host_merge_s"], 4),
            "device_wait_s": round(best_stats["device_wait_s"], 4),
            # Dispatch accounting: time spent invoking the compiled
            # step (post-warmup: pure per-step overhead) and dispatches
            # per second — scan fusion's target quantities.
            "dispatch_s": round(best_stats["dispatch_s"], 4),
            "steps_per_s": round(best_stats["steps_per_s"], 2),
            "n_steps": int(best_stats["n_chunks"]),
            "scan_chunks": int(best_stats["scan_chunks"]),
            "best_power_mw": round(res.argmin()["avg_power"] * 1e3, 4)}


def _spawn(mode: str, n: int, scan: int | None = None,
           ckpt_every_s: float | None = None) -> dict:
    # Workers are CPU rehearsals: the parent already ran JAX, and on a
    # chip host a child would contend for the chip it holds.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p])
    if mode == "stream":
        # The streaming executor's deployment mode on CPU hosts: shard
        # the chunk stream across one XLA host device per core (the
        # executor's pmap path picks them up automatically).  A single
        # XLA CPU device leaves the fused reduction step effectively
        # single-threaded (~2x slower on this 2-core reference box);
        # the dense path has no sharded execution mode, so it runs in
        # its own best (default single-device) configuration.
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count="
                            + str(os.cpu_count() or 1))
    cmd = [sys.executable, "-m", "benchmarks.stream_bench", "--worker",
           mode, str(n)]
    if scan is not None or ckpt_every_s is not None:
        cmd.append("-" if scan is None else str(scan))
    if ckpt_every_s is not None:
        cmd.append(str(ckpt_every_s))
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=3600,
        cwd=str(SRC.parent), env=env)
    if out.returncode != 0:
        return {"mode": mode, "n": n,
                "failed": out.stderr.strip().splitlines()[-1]
                if out.stderr.strip() else "worker died"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def _parity() -> dict:
    """Exact stream/dense agreement on the 10,880 reference grid."""
    from repro.core import pareto, stream, sweep

    dense = sweep.evaluate_grid(**REFERENCE_GRID)
    res = stream.stream_grid(**REFERENCE_GRID, chunk_size=4096,
                             track="all")
    df, sf = pareto.pareto_front(dense), res.pareto_front()
    return {
        "grid_configs": dense.n_configs,
        "argmin": all(res.argmin(f) == dense.argmin(f)
                      for f in sweep.FIELDS),
        "top_k": all(res.top_k(o) == dense.top_k(o, 4)
                     for o in res.objectives),
        "pareto_front": bool(np.array_equal(df.indices, sf.indices)
                             and np.array_equal(df.values, sf.values)),
    }


def rows():
    parity = _parity()
    assert all(parity[k] for k in ("argmin", "top_k", "pareto_front")), \
        f"stream/dense parity violated: {parity}"

    def median_worker(results):
        ok = [r for r in results if "configs_per_s" in r]
        if not ok:
            return results[-1]
        ok.sort(key=lambda r: r["configs_per_s"])
        return ok[len(ok) // 2]

    points = []
    out = []
    for n in (100_000, 1_000_000, 10_000_000):
        # Adjacent (stream, dense) runs so shared-host noise hits both.
        # The short sizes are frequency/scheduler-noise dominated on a
        # small host (worker-to-worker spread up to ~3x, either
        # direction), so they run three alternating pairs and each mode
        # reports its *median* worker — a single best-of would let one
        # boost window decide the ratio.
        pairs = 3 if n <= 1_000_000 else 1
        s_runs, d_runs = [], []
        for _ in range(pairs):
            s_runs.append(_spawn("stream", n))
            d_runs.append(_spawn("dense", n))
        s, d = median_worker(s_runs), median_worker(d_runs)
        points.append({"n": n, "stream": s, "dense": d})
        tag = f"{n:.0e}".replace("+0", "").replace("+", "")
        if "configs_per_s" in s:
            out.append((f"stream.{tag}.configs_per_s",
                        s["configs_per_s"],
                        f"steady {s.get('steady_configs_per_s', 0):.3g}/s "
                        f"rss {s['peak_rss_mb']:.0f}MB "
                        f"front {s.get('front_size', 0)} "
                        f"merge-stall {s.get('host_merge_s', 0):.3f}s"))
        else:
            out.append((f"stream.{tag}.FAILED", 0.0, str(s)))
        if "configs_per_s" in d:
            out.append((f"dense.{tag}.configs_per_s", d["configs_per_s"],
                        f"eval-only {d.get('eval_configs_per_s', 0):.3g}/s"
                        f" analysis {d.get('analysis_s', 0):.3f}s "
                        f"rss {d['peak_rss_mb']:.0f}MB"))
        else:
            out.append((f"dense.{tag}.skipped", 0.0,
                        d.get("skipped", d.get("failed", "?"))))

    # Scan-fused dispatch: stream-only points comparing auto-fused
    # (scan_chunks chosen from the step count) against forced per-chunk
    # dispatch (scan_chunks=1) at 10^7 and 10^8 configs — the dense
    # path cannot run 10^8 (the full channel grids alone are ~9 GB).
    # Each chunk's share of the per-dispatch fixed cost falls K-fold,
    # so the robust signal is the *dispatch count* (and dispatch_s per
    # step); note XLA CPU dispatch is synchronous, so dispatch_s also
    # absorbs blocked device compute — on accelerator backends it
    # isolates the launch overhead scan fusion amortizes.  1e7 runs are
    # noise-dominated on small hosts: alternate pairs, report medians.
    scan_fused = {}
    for n, tag, pairs, k_fused in ((10_000_000, "1e7", 2, 4),
                                   (100_000_000, "1e8", 1, 8)):
        # Explicit K for the fused arm: auto-K depends on the per-device
        # step count, so on many-core hosts it could resolve to 1 and
        # this comparison would silently measure nothing.
        f_runs, p_runs = [], []
        for _ in range(pairs):
            f_runs.append(_spawn("stream", n, scan=k_fused))
            p_runs.append(_spawn("stream", n, scan=1))
        fused = median_worker(f_runs)
        per_chunk = median_worker(p_runs)
        scan_fused[tag] = {"fused": fused, "per_chunk": per_chunk}
        if "configs_per_s" not in fused or "configs_per_s" not in per_chunk:
            out.append((f"stream.{tag}.scan_fused.FAILED", 0.0,
                        str(fused if 'configs_per_s' not in fused
                            else per_chunk)))
            continue
        out.append((
            f"stream.{tag}.scan_fused_configs_per_s",
            fused["configs_per_s"],
            f"K={fused.get('scan_chunks')} "
            f"{fused.get('n_steps')} dispatches "
            f"(vs {per_chunk.get('n_steps')} per-chunk) "
            f"rss {fused.get('peak_rss_mb', 0):.0f}MB"))
        out.append((
            f"stream.{tag}.dispatches_cut",
            round(per_chunk["n_steps"] / max(fused["n_steps"], 1), 2),
            f"per-chunk {per_chunk['n_steps']} dispatches "
            f"({per_chunk['dispatch_s']:.2f}s in-call) -> fused "
            f"{fused['n_steps']} ({fused['dispatch_s']:.2f}s); "
            f"throughput {fused['configs_per_s'] / per_chunk['configs_per_s']:.2f}x"))

    # Checkpoint overhead at 1e7: the fault-tolerance tentpole's cost
    # target is < 2% throughput loss at the default interval (30 s —
    # at this size that is the terminal snapshot plus at most a handful
    # of periodic ones).  The 1 s-interval row bounds the worst case
    # (a checkpoint nearly every macro step).  Single 1e7 runs carry a
    # few percent of shared-host noise, so the default-interval ratio
    # can read slightly negative; the write-time accounting
    # (checkpoint_write_s) is the noise-free number.
    base_1e7 = next(p for p in points if p["n"] == 10_000_000)["stream"]
    checkpoint_overhead = {"baseline": base_1e7}
    for tag, every_s in (("default", 30.0), ("1s", 1.0)):
        r = _spawn("stream", 10_000_000, ckpt_every_s=every_s)
        checkpoint_overhead[tag] = r
        if "configs_per_s" not in r or "configs_per_s" not in base_1e7:
            out.append((f"stream.1e7.ckpt_{tag}.FAILED", 0.0, str(r)))
            continue
        pct = 100.0 * (1.0 - r["configs_per_s"]
                       / base_1e7["configs_per_s"])
        checkpoint_overhead[f"overhead_pct_{tag}"] = round(pct, 2)
        out.append((
            f"stream.1e7.ckpt_{tag}.overhead_pct", round(pct, 2),
            f"every {every_s:g}s: {r['checkpoints_written']} snapshots, "
            f"{r['checkpoint_write_s']:.3f}s writing "
            f"({r['configs_per_s']:.3g}/s vs "
            f"{base_1e7['configs_per_s']:.3g}/s bare; target < 2% "
            f"at default interval)"))

    def ratio_at(n):
        p = next((p for p in points if p["n"] == n), None)
        if (p and "configs_per_s" in p["stream"]
                and "configs_per_s" in p["dense"]):
            return round(p["stream"]["configs_per_s"]
                         / p["dense"]["configs_per_s"], 2)
        return None

    s_small = points[0]["stream"].get("peak_rss_mb")
    s_big = points[-1]["stream"].get("peak_rss_mb")
    snapshot = {
        "parity_10880": parity,
        "points": points,
        # Per-chunk dispatch overhead vs lax.scan-fused multi-chunk
        # dispatch (exact parity preserved; see tests/test_backend.py).
        "scan_fused": scan_fused,
        # Fault-tolerance tentpole: durable checkpoint cost at 1e7
        # (default 30 s interval vs a 1 s worst case).
        "checkpoint_overhead_1e7": checkpoint_overhead,
        "stream_rss_growth_1e5_to_1e7":
            (round(s_big / s_small, 2) if s_small and s_big else None),
        # The regression PR 4 fixed (fused on-device reductions + async
        # double-buffered streaming) stays visible here: streaming must
        # hold >= 1.0 at every size, most critically at 1e5 where PR 3
        # recorded 0.37.  Per-point host_merge_s / device_wait_s above
        # record the merge-stall accounting behind it.
        "stream_vs_dense_at_1e5": ratio_at(100_000),
        "stream_vs_dense_at_1e6": ratio_at(1_000_000),
        "stream_vs_dense_at_1e7": ratio_at(10_000_000),
        "pr3_stream_vs_dense": {"1e5": 0.37, "1e6": 0.51, "1e7": 1.13},
        "pr1_dense_baseline_configs_per_s": 1_662_391.5,
    }
    BENCH_JSON.write_text(json.dumps(snapshot, indent=2) + "\n")

    out.append(("stream.parity_10880",
                1.0, "argmin/top-k/front exactly equal dense"))
    for n in (100_000, 1_000_000, 10_000_000):
        r = ratio_at(n)
        if r is not None:
            out.append((f"stream.vs_dense_{n:.0e}".replace("+0", ""),
                        r, "streaming/dense throughput ratio (>= 1.0)"))
    if s_small and s_big:
        out.append(("stream.rss_growth_1e5_to_1e7", s_big / s_small,
                    "bounded host memory: peak RSS ratio across 100x grid"))
    # Every timed number above comes from a JAX_PLATFORMS=cpu worker.
    return [(name, val, f"CPU; {derived}") for name, val, derived in out]


def main() -> None:
    if len(sys.argv) >= 4 and sys.argv[1] == "--worker":
        scan = None
        if len(sys.argv) >= 5 and sys.argv[4] != "-":
            scan = int(sys.argv[4])
        ckpt = float(sys.argv[5]) if len(sys.argv) >= 6 else None
        print(json.dumps(_worker(sys.argv[2], int(sys.argv[3]), scan,
                                 ckpt)))
        return
    print("name,value,derived")
    for name, val, derived in rows():
        print(f"{name},{val:.6g},{derived}")
    print(f"(snapshot written to {BENCH_JSON})")


if __name__ == "__main__":
    main()
