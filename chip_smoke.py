"""Bring-up smoke run of the DOSC sweep engine on a TPU.

    python chip_smoke.py              # one chip: device, study, reference,
                                      # invariant and service phases
    python chip_smoke.py --chips 4    # the 10^7 study on four chips vs one

Drives the engine's main path through the entry points a user calls:
``stream.stream_grid`` over the README's 10,009,600-config constrained
study, then the sweep service behind a Unix socket, driven by
``SweepClient``.  Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase exits 1.  Without a TPU, or outside a checkout of the
repository, it exits 2 and prints no result.

The times it prints are one smoke run on the named ``device_kind`` (cold
compile included where stated), not a benchmark.  The persistent
compilation cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

#: The README's constrained streaming study: 34 cuts x 2 x 2 x 2 x 50 x 2
#: x 2 x 2 x 92 = 10,009,600 configurations under a 15 ms latency budget
#: and a 25 MB/s MIPI cap, both compiled into the chunk step.
#: ``tests/test_tpu_compile.py`` compiles this study's step for a v5e.
STUDY_1E7 = dict(
    agg_nodes=("7nm", "16nm"), sensor_nodes=("7nm", "16nm"),
    weight_mems=("sram", "mram"), detnet_fps=tuple(np.linspace(5, 30, 50)),
    keynet_fps=(15.0, 30.0), num_cameras=(2, 4),
    mipi_energy_scale=(1.0, 2.0), camera_fps=tuple(np.linspace(20, 60, 92)),
    constraints={"latency": 15e-3, "mipi_bytes_per_s": 25e6})

#: Largest relative difference allowed between a chip float64 value and
#: the CPU's float64 value of the same configuration, applied only where
#: the two are not bitwise equal.  On a TPU v5e they are not: on 65,536
#: configurations of the study only 3-13% of the values of a channel
#: were bitwise the CPU's, and the largest relative difference was
#: 2.3e-14 (latency); every deliverable still picked the same
#: configurations.  1e-12 leaves about 40x of headroom for ulp ties.
REL_TOL = 1e-12

#: The repository's scalar <-> vectorized parity bound (tests/test_sweep.py).
SCALAR_TOL = 1e-6

#: Configurations of the study sampled for the chip-vs-CPU float64 check.
N_SAMPLE = 1 << 16


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations, and
    keeps each backend compile's program name and seconds."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s"}

    def __init__(self):
        import jax
        self.total = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.programs: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name=None, **_):
        key = self.EVENTS.get(event)
        if key is not None:
            self.total[key] += duration
        if key == "compile_s":
            self.programs.append((fun_name, duration))

    def mark(self):
        return dict(self.total), len(self.programs)

    def since(self, mark) -> dict:
        total, n = mark
        return {**{k: self.total[k] - total[k] for k in self.total},
                "compiled": [[name, s] for name, s in self.programs[n:]
                             if s >= 1.0]}


def emit(phase: str, ok: bool, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": ok, **fields}), flush=True)


def same_result(a, b) -> bool:
    """Bitwise equality of every deliverable of two ``StreamResult``s."""
    return (a.min_val == b.min_val and a.min_idx == b.min_idx
            and a.finite_counts == b.finite_counts
            and a.channel_min == b.channel_min
            and a.channel_max == b.channel_max
            and np.array_equal(a.topk_idx, b.topk_idx)
            and np.array_equal(a.topk_val, b.topk_val)
            and np.array_equal(a.front_indices, b.front_indices)
            and np.array_equal(a.front_values, b.front_values)
            and a.partial == b.partial)


def run_stats(res) -> dict:
    keys = ("total_s", "first_chunk_s", "configs_per_s",
            "steady_configs_per_s", "n_chunks", "scan_chunks",
            "host_merge_s", "device_wait_s", "dispatch_s", "fallback_chunks",
            "retries", "restarts", "elastic_replans", "chunks_reissued")
    return {k: res.stats[k] for k in keys}


def check_clean(res) -> None:
    """A restart loop must never hide a device fault."""
    bad = {k: res.stats[k] for k in ("retries", "restarts",
                                     "elastic_replans") if res.stats[k]}
    if bad:
        raise RuntimeError(f"recovery machinery fired: {bad}")


def phase_study(study, clock, kind, devices=None):
    from repro.core import stream

    mark = clock.mark()
    t0 = time.perf_counter()
    res = stream.stream_grid(**study, devices=devices)
    wall = time.perf_counter() - t0
    check_clean(res)
    emit("study", True, note=f"one smoke run on {kind}, not a benchmark",
         n_configs=res.n_configs, n_devices=res.n_devices, wall_s=wall,
         cold_configs_per_s=res.n_configs / wall, **clock.since(mark),
         feasible=res.finite_counts["avg_power"],
         front_size=int(res.front_indices.size), stats=run_stats(res))
    return res


def channel_diffs(study, n_sample):
    """Largest relative chip-vs-CPU difference per channel on a strided
    sample of the study, and the share of bitwise-equal values."""
    import jax
    from repro.core import backend as B
    from repro.core import sweep as SW

    grid = {k: v for k, v in study.items() if k != "constraints"}
    S, axis_vals, _ = SW.build_axes(**grid)
    shape = tuple(a.size for a in axis_vals)
    n = int(np.prod(shape))
    flat = np.unique(np.linspace(0, n - 1, min(n_sample, n)).astype(np.int64))
    fields = SW.kernel_fields(S)
    ev = B.cached_dense_eval("xla", S, shape, fields)
    out = {}
    with jax.enable_x64(True):
        for name, dev in (("chip", jax.devices()[0]),
                          ("cpu", jax.devices("cpu")[0])):
            with jax.default_device(dev):
                r = ev(tuple(map(jax.numpy.asarray, axis_vals)),
                       jax.numpy.asarray(flat))
                out[name] = {f: np.asarray(r[f]) for f in fields}
    rel, bitwise = {}, {}
    for f in fields:
        a, b = out["chip"][f], out["cpu"][f]
        fin = np.isfinite(b)
        if not np.array_equal(fin, np.isfinite(a)):
            raise RuntimeError(f"{f}: chip and CPU differ in NaN pattern")
        d = np.abs(a[fin] - b[fin]) / np.maximum(np.abs(b[fin]), 1e-300)
        rel[f] = float(d.max()) if d.size else 0.0
        bitwise[f] = float(np.mean(a[fin] == b[fin])) if d.size else 1.0
    return rel, bitwise, int(flat.size)


def phase_reference(study, res, clock):
    """The same study on the CPU, named as the float64 reference."""
    import jax
    from repro.core import partition, stream

    mark = clock.mark()
    t0 = time.perf_counter()
    ref = stream.stream_grid(**study, devices=jax.devices("cpu")[:1])
    wall = time.perf_counter() - t0
    check_clean(ref)
    rel, share, n = channel_diffs(study, N_SAMPLE)
    bitwise = same_result(res, ref)
    problems = []
    if not bitwise:
        # Same answers up to REL_TOL: counts exact, every reduced value
        # within the tolerance, argmin/top-k/front on the same configs.
        def close(x, y):
            x, y = np.asarray(x, float), np.asarray(y, float)
            fin = np.isfinite(y)
            return (np.array_equal(fin, np.isfinite(x)) and np.all(
                np.abs(x[fin] - y[fin]) <= REL_TOL * np.abs(y[fin])))

        if res.finite_counts != ref.finite_counts:
            problems.append("counts")
        for f in ref.min_val:
            if not close(res.min_val[f], ref.min_val[f]):
                problems.append(f"min_val:{f}")
            if res.min_idx[f] != ref.min_idx[f]:
                problems.append(f"min_idx:{f}")
        if not close(res.topk_val, ref.topk_val):
            problems.append("topk_val")
        if not np.array_equal(res.topk_idx, ref.topk_idx):
            problems.append("topk_idx")
        if not np.array_equal(res.front_indices, ref.front_indices):
            problems.append(f"front_indices ({res.front_indices.size} vs "
                            f"{ref.front_indices.size})")
        elif not close(res.front_values, ref.front_values):
            problems.append("front_values")
    worst_sample = max(rel.values())
    if worst_sample > REL_TOL:
        problems.append(f"sample rel diff {worst_sample:.3g} > {REL_TOL}")
    # The scalar path at the chip's argmin of each objective: the repo's
    # scalar <-> vectorized parity bound.
    scalar_rel = {}
    for f in res.objectives:
        best = res.argmin(f)
        pt = partition.evaluate_cut(
            best["cut"], agg_node=best["agg_node"],
            sensor_node=best["sensor_node"],
            sensor_weight_mem=best["weight_mem"],
            detnet_fps=best["detnet_fps"], keynet_fps=best["keynet_fps"],
            num_cameras=int(best["num_cameras"]),
            camera_fps=best["camera_fps"],
            mipi_energy_scale=best["mipi_energy_scale"])
        scalar_rel[f] = abs(best[f] - getattr(pt, f)) / abs(getattr(pt, f))
    if max(scalar_rel.values()) > SCALAR_TOL:
        problems.append(f"scalar parity {scalar_rel}")
    emit("reference", not problems, bitwise=bitwise, rel_tol=REL_TOL,
         problems=problems, sample_configs=n, sample_max_rel_diff=rel,
         sample_bitwise_share=share, scalar_rel_diff=scalar_rel,
         cpu_wall_s=wall, **clock.since(mark))
    return not problems


def phase_invariant(grid, clock):
    """Dense ``evaluate_grid`` and ``stream_grid`` agree exactly."""
    from repro.core import pareto, stream, sweep

    mark = clock.mark()
    dense = sweep.evaluate_grid(**grid)
    res = stream.stream_grid(**grid, track="all")
    check_clean(res)
    bad = [f for f in sweep.FIELDS if res.argmin(f) != dense.argmin(f)]
    bad += [f"top_k:{o}" for o in res.objectives
            if res.top_k(o) != dense.top_k(o, res.topk_idx.shape[1])]
    bad += [f"count:{f}" for f in sweep.FIELDS if res.finite_counts[f]
            != int(np.isfinite(dense.data[f]).sum())]
    df, sf = pareto.pareto_front(dense), res.pareto_front()
    if not (np.array_equal(df.indices, sf.indices)
            and np.array_equal(df.values, sf.values)):
        bad.append("front")
    emit("invariant", not bad, n_configs=res.n_configs, mismatches=bad,
         front_size=int(sf.indices.size), **clock.since(mark))
    return res, not bad


def phase_service(study, grid, study_res, grid_res, clock):
    """In-process ``SweepService`` behind a Unix socket, two tenants."""
    from repro.core import stream
    from repro.core.client import SweepClient
    from repro.core.service import SweepRequest, SweepService
    from repro.runtime.transport import SweepServer

    mark = clock.mark()
    # A tighter latency budget is a runtime bound of the same compiled
    # step, so this third request costs no compile.
    tight = dict(study["constraints"], latency=12e-3)
    t0 = time.perf_counter()
    warm = stream.stream_grid(**dict(study, constraints=tight))
    warm_s = time.perf_counter() - t0
    check_clean(warm)
    grid_kw = {k: v for k, v in study.items() if k != "constraints"}
    reqs = [
        (SweepRequest(grid=grid_kw, constraints=study["constraints"],
                      tenant="interactive"), study_res),
        (SweepRequest(grid=grid, track="all", tenant="batch"), grid_res),
        (SweepRequest(grid=grid_kw, constraints=tight, tenant="batch"),
         warm),
    ]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        svc = SweepService(spool_dir=os.path.join(tmp, "spool"))
        svc.set_tenant("interactive", weight=3.0)
        svc.set_tenant("batch", weight=1.0)
        with SweepServer(svc, unix_path=os.path.join(tmp, "s.sock"),
                         own_service=True) as server, \
                SweepClient(server.address) as cli:
            t0 = time.perf_counter()
            tickets = [cli.submit(r, client_id=f"chip-smoke-{i}")
                       for i, (r, _) in enumerate(reqs)]
            served = [t.result(timeout=900) for t in tickets]
            served_s = time.perf_counter() - t0
            again = cli.submit(reqs[0][0], client_id="chip-smoke-0")
            deduped = (again.id == tickets[0].id
                       and cli.health()["counters"]["deduped"] >= 1)
            counters = cli.health()["counters"]
    exact = [same_result(s, ref) for s, (_, ref) in zip(served, reqs)]
    for s in served:
        check_clean(s)
    ok = all(exact) and deduped and counters["failed"] == 0
    emit("service", ok, requests=len(reqs), bitwise=exact, deduped=deduped,
         served_wall_s=served_s, warm_inprocess_wall_s=warm_s,
         completed=counters["completed"], failed=counters["failed"],
         **clock.since(mark))
    return ok


def phase_multichip(study, chips, clock, kind):
    """The study on ``chips`` devices via pmap vs the same on one."""
    import jax

    devs = jax.devices()[:chips]
    many = phase_study(study, clock, kind, devices=devs)
    one = phase_study(study, clock, kind, devices=devs[:1])
    ok = same_result(many, one)
    emit("multichip", ok, chips=chips, identical=ok)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="with N > 1: run only the 10^7 study on N chips "
                         "and on one, and require identical results")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import jax
        devices = jax.devices()
    except Exception as e:          # no backend could start at all
        print(f"JAX found no usable backend: {e}", file=sys.stderr)
        return 2
    d0 = devices[0]
    if d0.platform != "tpu" or len(devices) < args.chips:
        print(f"needs {args.chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {d0.platform} ({d0.device_kind})",
              file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    emit("device", True, **device, compile_cache=cache_dir)

    from benchmarks.sweep_bench import GRID

    def run(fn, *a):
        try:
            return fn(*a)
        except Exception as e:      # report, then go on to the next phase
            emit(fn.__name__.replace("phase_", ""), False,
                 error=f"{type(e).__name__}: {e}")
            return None

    if args.chips > 1:
        results = [run(phase_multichip, STUDY_1E7, args.chips, clock,
                       d0.device_kind)]
    else:
        study_res = run(phase_study, STUDY_1E7, clock, d0.device_kind)
        ran = study_res is not None
        ref_ok = ran and run(phase_reference, STUDY_1E7, study_res, clock)
        inv = run(phase_invariant, GRID, clock)
        served_ok = (ran and inv is not None
                     and run(phase_service, STUDY_1E7, GRID, study_res,
                             inv[0], clock))
        results = [ran, ref_ok, inv is not None and inv[1], served_ok]
    ok = all(results)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
