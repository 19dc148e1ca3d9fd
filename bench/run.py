"""Benchmark of the DOSC sweep engine: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the checkout's root: its configuration (``bench/configs/<config>.json``),
its traffic (``bench/traffic/<traffic>.json``, read by ``traffic.py``),
and one reader per metric (``bench/metrics/<metric>.py``, a ``read(run)``
that returns a number, or ``None`` when it finds nothing to read).

A ``closed_loop`` cell runs whole ``stream.stream_grid`` studies back to
back; an ``open_loop`` cell sends sub-studies through ``SweepClient`` to
a ``SweepService`` with a spool behind a ``SweepServer`` on TCP
loopback.  Set-up warms up the cell's own programs; the window then
lasts ``--seconds``.  Once the window has closed, a sample of its
answers drawn from the seed is compared with the plain float64
reference of ``reference.py`` (``compare.py`` decides ``correct``).

The last line of standard output is the result object; earlier lines
give the set-up breakdown, the compilations inside the window, each
study's or the slowest tickets' times, the open loop's lateness, the
host's stalls in the window and, with ``--trace 1``, each device's busy
time, the program's spans and the chunk step's device time by named
scope (``trace_spans.py``), and the traced run's end-to-end readings.
Without a TPU, or with fewer chips than the cell asks for, or outside a
checkout that holds the program's ``src/``, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import gc                                                     # noqa: E402
import importlib.util                                         # noqa: E402
import json                                                   # noqa: E402
import math                                                   # noqa: E402
import os                                                     # noqa: E402
import queue                                                  # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
import tempfile                                               # noqa: E402
import threading                                              # noqa: E402
from pathlib import Path                                      # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, under the checkout's root: a fixed
#: directory, whatever the environment says, so that only a cell's first
#: run there compiles and two checkouts share nothing.
CACHE_DIR = Path("bench") / ".jax_cache"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import numpy as np                                            # noqa: E402

import compare                                                # noqa: E402
import reference                                              # noqa: E402
import trace as tracemod                                      # noqa: E402
import trace_spans                                            # noqa: E402
import traffic as traffic_mod                                 # noqa: E402


def log(**fields) -> None:
    """One JSON line of the run's record on standard output."""
    print(json.dumps(fields, default=float), flush=True)


def load_cell(root: Path, name: str):
    """``(benchmark, cell, configuration, traffic)`` of a cell name.  A
    configuration whose objectives or constraints name a channel that
    the plain reference cannot check is refused here, before any work."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / cfg["file"]).read_text())
    unknown = [f for f in (*config["objectives"],
                           *config.get("constraints", {}))
               if f not in reference.CHANNELS]
    if unknown:
        raise ValueError(f"configuration {cfg['name']!r} names channels "
                         f"the reference lacks: {', '.join(unknown)} (it "
                         f"has {', '.join(reference.CHANNELS)})")
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str, traced: bool) -> list:
    """The metrics a cell reports: its end-to-end ones untraced, its
    per-layer ones traced (a metric without ``workloads`` goes with
    every cell that reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metric(root: Path, name: str, run: dict):
    """Value of metric ``name`` by its reader ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations, and
    counts backend compiles."""

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
                "compiles": len(self.programs) - n,
                "compiled": [[name, s] for name, s in self.programs[n:]
                             if s >= 0.5]}


class StallWatch:
    """Host stalls in the window: a thread that asks to wake every
    ``tick`` seconds records each wake that came more than ``late`` s
    late (the whole process held: the GIL, the scheduler or the VM),
    the garbage collector's pauses longer than ``late``, and the CPU
    time the hypervisor stole from the machine (``/proc/stat``)."""

    def __init__(self, tick: float = 0.02, late: float = 0.1):
        self.tick, self.late = tick, late
        self.wakes: list = []
        self.gc: list = []
        self._gc_t0 = None
        self._done = threading.Event()
        self._thread = None

    @staticmethod
    def _steal_s():
        try:
            with open("/proc/stat") as f:
                cpu = f.readline().split()
            return int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            d = time.perf_counter() - self._gc_t0
            if d > self.late:
                self.gc.append([self._gc_t0 - self.t0, d,
                                info.get("generation")])

    def _watch(self):
        due = time.perf_counter() + self.tick
        while not self._done.wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            if now - due > self.late:
                self.wakes.append([due - self.t0, now - due])
            due = now + self.tick

    def start(self, t0: float) -> None:
        self.t0 = t0
        self.steal0 = self._steal_s()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def stop(self) -> dict:
        self._done.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        steal = self._steal_s()
        return {"late_wakes": len(self.wakes),
                "late_s_max": max((w[1] for w in self.wakes), default=0.0),
                "worst": sorted(self.wakes, key=lambda w: -w[1])[:8],
                "gc_pauses": self.gc[:8],
                "cpu_steal_s": (steal - self.steal0
                                if steal is not None
                                and self.steal0 is not None else None)}


class StepWatch:
    """The chunk-step programs a traced window runs, kept with their
    argument shapes from the window's start, for their op-name paths:
    a TPU trace's op events carry none, so :meth:`op_paths` compiles
    each program once more after the window (a hit in the persistent
    compilation cache) and reads them from its HLO text."""

    def __init__(self):
        from repro.core import backend
        self._backend = backend
        self._cached_step = backend.cached_step
        self.steps: dict = {}
        backend.cached_step = self._spy

    def _spy(self, *a, **kw):
        import jax
        fn = self._cached_step(*a, **kw)

        def step(*xs):
            if id(fn) not in self.steps:
                self.steps[id(fn)] = (fn, jax.tree_util.tree_map(
                    self._abstract, xs))
            return fn(*xs)
        return step

    @staticmethod
    def _abstract(x):
        """What the compiled program was keyed on, without the data: a
        committed array's sharding and each value's weak type count."""
        import jax
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)

    def close(self) -> None:
        self._backend.cached_step = self._cached_step

    def op_paths(self) -> dict:
        import jax
        paths: dict = {}
        with jax.enable_x64(True):
            for fn, shapes in self.steps.values():
                paths.update(trace_spans.hlo_op_paths(
                    fn.lower(*shapes).compile().as_text()))
        return paths


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def check_clean(res) -> None:
    """A restart or retry inside a study must never hide a device fault."""
    bad = {k: res.stats.get(k) for k in ("retries", "restarts",
                                         "elastic_replans")
           if res.stats.get(k)}
    if bad:
        raise RuntimeError(f"recovery machinery fired: {bad}")


def run_closed_loop(config, traffic, seed, seconds, devs, clock, run,
                    trace_dir=None):
    """Whole studies back to back until ``seconds`` have elapsed; the
    last study to start is finished.  Returns ``(answers, failures)``."""
    from repro.core import stream

    common = dict(objectives=tuple(config["objectives"]),
                  top_k=config["top_k"], devices=devs)
    wstudy, wb = traffic_mod.draw_study(
        config, np.random.default_rng([int(seed), 2]))
    kw = dict(traffic_mod.stream_kwargs(wstudy), constraints=wb or None,
              **common)
    plan = stream.plan_stream(**kw)
    quantum = plan.chunk * plan.scan * len(devs)
    t = time.perf_counter()
    mark = clock.mark()
    # The grid's last dispatches: they compile the step (twice on the
    # pmap path, whose first dispatch takes a host carry) and, where the
    # last chunks overflow the survivor buffer, the dense evaluator that
    # re-derives their survivors.
    with span("bench.warmup"):
        stream.stream_grid(**kw, flat_range=(max(
            0, plan.n_total - traffic["warmup_quanta"] * quantum),
            plan.n_total))
    run["setup"]["warmup_s"] = time.perf_counter() - t
    run["setup"]["warmup_compile"] = clock.since(mark)
    gen = traffic_mod.studies(config, seed)
    answers, failures = [], []
    start_window(run, clock, trace_dir)
    t0 = run["window_start"]
    with span("bench.window"):
        while time.perf_counter() - t0 < seconds:
            study, budgets = next(gen)
            ts = time.perf_counter()
            try:
                with span("bench.study"):
                    res = stream.stream_grid(
                        **traffic_mod.stream_kwargs(study),
                        constraints=budgets or None, **common)
                check_clean(res)
            except Exception as e:          # a study that never answers
                failures.append(f"{type(e).__name__}: {e}")
                break
            te = time.perf_counter()
            run["studies"].append({"n_configs": res.n_configs,
                                   "t_start": ts, "t_end": te,
                                   "stats": dict(res.stats)})
            answers.append((study, budgets, res))
    end_window(run, trace_dir)
    return answers, failures


def run_open_loop(config, traffic, seed, seconds, devs, clock, run,
                  trace_dir=None):
    """Sub-studies through the served path at fixed seeded arrival
    times.  Returns ``(answers, failures)``; a refused ticket is
    counted in ``run["refused"]``."""
    from repro.core.client import SweepClient
    from repro.core.service import SweepRequest, SweepService
    from repro.runtime.admission import BackpressureError
    from repro.runtime.transport import SweepServer

    def request(study, budgets, tenant):
        return SweepRequest(grid=traffic_mod.stream_kwargs(study),
                            constraints=budgets or None,
                            objectives=tuple(config["objectives"]),
                            top_k=config["top_k"], tenant=tenant)

    spool = tempfile.mkdtemp(prefix="bench-spool-")
    t = time.perf_counter()
    svc = SweepService(spool_dir=os.path.join(spool, "spool"))
    for name, ten in traffic["tenants"].items():
        svc.set_tenant(name, weight=ten["weight"],
                       max_pending=ten.get("max_pending"))
    server = SweepServer(svc, host="127.0.0.1", port=0,
                         own_service=True).start()
    run["setup"]["server_s"] = time.perf_counter() - t
    try:
        t = time.perf_counter()
        mark = clock.mark()
        wrng = np.random.default_rng([int(seed), 2])
        names = list(traffic["tenants"])
        with span("bench.warmup"), SweepClient(server.address) as cli:
            for i in range(traffic["warmup_queries"]):
                study, budgets = traffic_mod.draw_query(config, traffic,
                                                        wrng)
                cli.submit(request(study, budgets, names[i % len(names)]),
                           client_id=f"warmup-{seed}-{i}").result(
                               timeout=1200)
        run["setup"]["warmup_s"] = time.perf_counter() - t
        run["setup"]["warmup_compile"] = clock.since(mark)
        sched = traffic_mod.queries(config, traffic, seed, seconds)
        todo: queue.Queue = queue.Queue()
        for i, item in enumerate(sched):
            todo.put((i, item))
        records: list = [None] * len(sched)
        answers: list = [None] * len(sched)
        c0 = dict(server.counters)
        backlog = []
        done = threading.Event()
        start_window(run, clock, trace_dir)
        t0 = run["window_start"]
        deadline = t0 + seconds + 60.0

        def sender():
            with SweepClient(server.address) as cli:
                while True:
                    try:
                        i, (due, study, budgets, tenant) = todo.get_nowait()
                    except queue.Empty:
                        return
                    wait = t0 + due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    rec = {"due": due, "tenant": tenant, "latency_s": None,
                           "executor_s": None, "clocks": None, "error": None,
                           "late_s": time.perf_counter() - (t0 + due)}
                    try:
                        with span("bench.submit"):
                            tk = cli.submit(request(study, budgets, tenant),
                                            client_id=f"bench-{seed}-{i}")
                        with span("bench.result"):
                            res = tk.result(
                                timeout=max(1.0,
                                            deadline - time.perf_counter()))
                        rec["latency_s"] = time.perf_counter() - (t0 + due)
                        rec["executor_s"] = float(res.stats["total_s"])
                        rec["clocks"] = [res.stats.get(k) for k in (
                            "device_wait_s", "host_merge_s",
                            "checkpoint_write_s")]
                        answers[i] = (study, budgets, res)
                    except BackpressureError:
                        rec["error"] = "refused"
                    except Exception as e:   # never came, or failed
                        rec["error"] = f"{type(e).__name__}: {e}"
                    records[i] = rec

        def monitor():
            while not done.wait(0.25):
                backlog.append((time.perf_counter() - t0,
                                svc._queue.depth + len(svc._running)))

        with span("bench.window"):
            mon = threading.Thread(target=monitor, daemon=True)
            mon.start()
            threads = [threading.Thread(target=sender, daemon=True)
                       for _ in range(traffic["senders"])]
            for th in threads:
                th.start()
            for th in threads:
                th.join(max(1.0, deadline + 30.0 - time.perf_counter()))
            done.set()
            mon.join()
        end_window(run, trace_dir)
        c1 = dict(server.counters)
        run["wire_bytes"] = ((c1["bytes_in"] - c0["bytes_in"])
                             + (c1["bytes_out"] - c0["bytes_out"]))
        run["backlog"] = backlog
    finally:
        server.close(drain=False)
        shutil.rmtree(spool, ignore_errors=True)
    failures = []
    run["refused"] = 0
    for i, rec in enumerate(records):
        if rec is None:
            rec = records[i] = {"due": sched[i][0], "tenant": sched[i][3],
                                "latency_s": None, "executor_s": None,
                                "late_s": None, "error": "never sent"}
        if rec["error"] == "refused":
            run["refused"] += 1
        elif rec["error"] is not None:
            failures.append(rec["error"])
    run["tickets"] = records
    run["completed"] = sum(r["latency_s"] is not None for r in records)
    return [a for a in answers if a is not None], failures


def start_window(run, clock, trace_dir) -> None:
    """Close set-up and open the window (and the profiler's trace)."""
    if trace_dir:
        import jax
        run["step_watch"] = StepWatch()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host spans only, no Python calls
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    run["window_mark"] = clock.mark()
    run["window_start"] = time.perf_counter()
    run["setup_s"] = run["window_start"] - T_START
    run["stall_watch"] = StallWatch()
    run["stall_watch"].start(run["window_start"])


def end_window(run, trace_dir) -> None:
    run["window_end"] = time.perf_counter()
    run["stalls"] = run.pop("stall_watch").stop()
    if trace_dir:
        import jax
        jax.profiler.stop_trace()
        run["step_watch"].close()


def reduce_trace(run, trace_dir, planes) -> None:
    """``run["trace"]`` (``trace.py``'s reduction) and ``run["spans"]``
    (``trace_spans.py``'s, with the step ops' named scopes) from the
    window's trace, each logged; both ``None`` where no device op ran."""
    clock = {"t": time.perf_counter()}

    def lap(name):
        t = time.perf_counter()
        clock[name], clock["t"] = t - clock["t"], t

    events = trace_spans.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    lap("load_s")
    run["trace"] = tracemod.reduce(trace_spans.trace_events(events),
                                   planes=planes)
    lap("reduce_s")
    run["spans"] = None
    watch = run.pop("step_watch")
    if not run["trace"]:
        return
    log(trace_devices=run["trace"]["devices"])
    paths = watch.op_paths()
    lap("recompile_s")
    run["spans"] = red = trace_spans.reduce(
        events, planes=planes, op_paths=paths, base=run["trace"])
    lap("spans_s")
    log(program_spans=red["program_spans"])
    log(idle_by_span=red["idle_by_span"], idle_gaps=red["idle_gaps"],
        ticket_idle=red["ticket_idle"])
    log(scope_s=red["scope_s"], scope_leaf_s=red["scope_leaf_s"])
    log(span_readings=trace_spans.readings(red, run))
    del clock["t"]
    log(trace_reduce=dict(clock, events=len(events),
                          step_programs=len(watch.steps),
                          op_paths=len(paths)))


def check(answers, config, traffic, seed) -> dict:
    """Compare a seeded sample of the window's answers with the plain
    reference; returns the worst reading of each compared number."""
    rng = np.random.default_rng([int(seed), 3])
    n = min(len(answers), int(traffic.get("sample", 1)))
    pick = sorted(rng.choice(len(answers), size=n, replace=False)) \
        if n else []
    readings = []
    for i in pick:
        study, budgets, res = answers[i]
        ref = reference.solve(study, budgets, config["objectives"],
                              config["top_k"])
        readings.append(compare.compare(res, ref, study, budgets,
                                        config["objectives"]))
    return compare.worst(readings), len(pick)


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic = load_cell(root, args.workload)
    except (OSError, KeyError, ValueError, StopIteration) as e:
        print(f"cannot load cell {args.workload!r}: {e}", file=sys.stderr)
        return 2
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"the program's src/ is not in {root}", file=sys.stderr)
        return 2
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    t = time.perf_counter()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax_import_s = time.perf_counter() - t
    t = time.perf_counter()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"JAX found no usable backend: {e}", file=sys.stderr)
        return 3
    backend_s = time.perf_counter() - t
    chips = int(cell["chips"])
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        print(f"cell {cell['name']} needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 3
    devs = devices[:chips]
    clock = CompileClock()
    t = time.perf_counter()
    import repro.core.stream  # noqa: F401  (import time is set-up)
    run = {"setup": {"import_s": time.perf_counter() - T_START,
                     "jax_import_s": jax_import_s,
                     "backend_start_s": backend_s,
                     "program_import_s": time.perf_counter() - t},
           "studies": [], "tickets": []}
    mark0 = clock.mark()

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    loop = {"closed_loop": run_closed_loop,
            "open_loop": run_open_loop}[traffic["kind"]]
    answers, failures = loop(config, traffic, args.seed, args.seconds,
                             devs, clock, run, trace_dir)
    window = clock.since(run["window_mark"])
    run["setup"]["compile"] = {
        k: v for k, v in clock.since(mark0).items() if k != "compiled"}
    log(setup=dict(run["setup"], setup_s=run["setup_s"]))
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    summary = {"window_s": run["window_end"] - run["window_start"],
               "studies": len(run["studies"]),
               "tickets": len(run["tickets"]),
               "compiles_in_window": window["compiles"],
               "study_s": [s["t_end"] - s["t_start"]
                           for s in run["studies"]],
               # each study's executor clocks: total, device wait, host
               # merge, dispatch, first chunk (StreamResult.stats)
               "study_clocks": [[s["stats"].get(k) for k in (
                   "total_s", "device_wait_s", "host_merge_s",
                   "dispatch_s", "first_chunk_s")]
                   for s in run["studies"]]}
    if run["tickets"]:
        late = [t["late_s"] for t in run["tickets"]
                if t["late_s"] is not None]
        summary.update(completed=run["completed"], refused=run["refused"],
                       latency_samples=sum(t["latency_s"] is not None
                                           for t in run["tickets"]),
                       generator_late_max_s=max(late, default=None),
                       generator_late_p95_s=(sorted(late)[int(0.95 * (
                           len(late) - 1))] if late else None),
                       backlog_end=(run["backlog"][-1][1]
                                    if run.get("backlog") else None),
                       backlog_max=max((b for _, b in run.get("backlog",
                                                              ())),
                                       default=None),
                       # the slowest tickets: due, late, latency, executor,
                       # and the executor's device wait, host merge and
                       # checkpoint write
                       slowest=[[t["due"], t["late_s"], t["latency_s"],
                                 t["executor_s"], *(t.get("clocks") or ())]
                                for t in sorted(
                                     (t for t in run["tickets"]
                                      if t["latency_s"] is not None),
                                     key=lambda t: -t["latency_s"])[:8]])
    log(window=summary, compiled_in_window=window["compiled"])
    log(host_stalls=run["stalls"])
    if trace_dir:
        reduce_trace(run, trace_dir,
                     [tracemod.plane_name(d) for d in devs])

    numbers, n_checked = check(answers, config, traffic, args.seed)
    ok = compare.within(numbers) and not failures and n_checked > 0
    metrics = {}
    for m in cell_metrics(bench, cell["name"], bool(args.trace)):
        v = read_metric(root, m["name"], run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(ok),
           "attempted": len(run["tickets"]) or len(run["studies"])
           + len(failures),
           "failed": len(failures) + run.get("refused", 0),
           "metrics": metrics, "device": device}
    if args.trace and run.get("trace"):
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        out["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                            "idle_gaps": run["spans"]["idle_gaps"]}
    if args.trace:
        log(traced_end_to_end={
            m["name"]: read_metric(root, m["name"], run)
            for m in cell_metrics(bench, cell["name"], False)
            if m["name"] != "setup_s"})
    if failures:
        log(failures=failures[:5])
    log(answers_compared=n_checked)
    checks = {k: {"value": (numbers[k] if math.isfinite(numbers[k])
                            else str(numbers[k])),
                  "limit": compare.LIMITS[k]} for k in compare.LIMITS}
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
