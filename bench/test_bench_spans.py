"""The spans' and scopes' reduction: additive to trace.py, and what it
charges and sums on a synthetic trace and on one recorded here."""

import importlib.util
import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import trace as T  # noqa: E402
import trace_spans as S  # noqa: E402
from test_bench_trace import DEV0, DEV1, HOST, ev, synthetic  # noqa: E402

OLD_KEYS = ("window_s", "busy_s", "idle_share", "devices", "program_s",
            "device_ops")
W, C, M = "python#1", "python#2", "python#0"   # worker, connection, main


def reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(thread, name, start_ms, dur_ms, **meta):
    return ev(HOST, "python", name, start_ms, dur_ms) + (meta, thread)


def op(name, start_ms, dur_ms, program):
    return ev(DEV0, T.OP_LINE, name, start_ms, dur_ms) + (
        {"program": program}, T.OP_LINE)


#: The op-name paths the compiled step's HLO text gives the ops of
#: served(); fusion.d is the dense evaluator's.
OP_PATHS = {
    ("jit_step", "while.1"): "jit(step)/while",
    ("jit_step", "fusion.a"): "jit(step)/while/body/closed_call/eq1_11/mul",
    ("jit_step", "fusion.b"): "jit(step)/while/body/closed_call/"
    "chunk_partials/dominance_filter/jit(searchsorted)/while/body/gather",
    ("jit_step", "fusion.c"): "jit(step)/fold_chunk/sort",
    ("jit_evalfn", "fusion.d"): "jit(evalfn)/eq1_11/mul"}


def served():
    """A 100 ms window of one ticket T1 on one device.  The worker
    thread claims, executes and journals it; the connection thread
    admits and encodes it; the client decodes it.  The device runs the
    step 20-40 ms (a loop with two ops nested in it) and 50-55 ms, and
    the dense evaluator 80-85 ms."""
    return [
        span(M, "bench.window", 0, 100),
        span(M, "bench.submit", 0, 20),
        span(M, "bench.result", 20, 80),
        span(C, "repro.service.admit", 2, 6, client_id="c1"),
        span(C, "repro.service.journal", 3, 4, ticket="T1",
             state="queued"),
        span(W, "repro.service.start", 10, 2, ticket="T1", queued_s=0.01),
        span(W, "repro.service.journal", 10.5, 1, ticket="T1",
             state="running"),
        span(W, "repro.service.execute", 12, 48, ticket="T1"),
        span(W, "repro.stream.grid", 13, 46, ticket="T1"),
        span(W, "repro.stream.fetch", 40, 10, step=0),
        span(W, "repro.service.journal", 60, 10, ticket="T1",
             state="done"),
        span(C, "repro.wire.encode", 70, 5, ticket="T1"),
        span(M, "repro.wire.decode", 76, 2, ticket="T1"),
        ev(DEV0, T.MODULE_LINE, "jit_step(1)", 20, 20),
        op("%while.1 = (s64[]) while(%t)", 20, 20, "jit_step"),
        op("fusion.a", 20, 10, "jit_step"),
        op("fusion.b", 30, 5, "jit_step"),
        ev(DEV0, T.MODULE_LINE, "jit_step(2)", 50, 5),
        op("fusion.c", 50, 5, "jit_step"),
        ev(DEV0, T.MODULE_LINE, "jit_evalfn(3)", 80, 5),
        op("fusion.d", 80, 5, "jit_evalfn"),
    ]


RUN = {"completed": 1, "studies": [{"n_configs": 5e8, "t_start": 0.0,
                                    "t_end": 0.1,
                                    "stats": {"total_s": 0.1,
                                              "device_wait_s": 0.05,
                                              "host_merge_s": 0.01}}],
       "tickets": [{"latency_s": 0.08, "executor_s": 0.046}],
       "wire_bytes": 1000.0, "setup_s": 1.0, "window_start": 0.0}


@pytest.mark.parametrize("events,planes", [
    (synthetic(), None), (synthetic(), [DEV0, DEV1, "/device:TPU:2"]),
    (served(), None)])
def test_every_number_trace_py_gives_stays_the_same(events, planes):
    old = T.reduce([e[:5] for e in events], planes=planes)
    new = S.reduce(events, planes=planes)
    for k in OLD_KEYS:
        assert new[k] == old[k], k
    assert ([g[1] for g in new["idle_gaps"]]
            == [g[1] for g in old["idle_gaps"]])
    # As run.py calls it: trace.py's reduction of the events it keeps.
    given = S.reduce(events, planes=planes,
                     base=T.reduce(S.trace_events(events), planes=planes))
    assert given == new


@pytest.mark.parametrize("events", [synthetic(), served()])
def test_every_existing_reader_reads_the_same(events):
    """Each accepted metric's reader gives the same value from the
    reduction with spans as from trace.py's."""
    names = [p.stem for p in (BENCH / "metrics").glob("*.py")]
    for name in names:
        read = reader(name)
        a = read(dict(RUN, trace=T.reduce([e[:5] for e in events])))
        b = read(dict(RUN, trace=S.reduce(events)))
        assert a == b or (math.isnan(a) and math.isnan(b)), name


def test_idle_is_cut_at_span_edges_and_charged_to_the_innermost_span():
    r = S.reduce(served())
    got = {k: v * 1e3 for k, v in r["idle_by_span"].items()}
    # 0-20 ms: the sender's submit, then the admission and its journal
    # on the connection thread, then the worker's claim and executor.
    assert got == pytest.approx({
        "bench.submit": 4.0, "repro.service.admit": 2.0,
        "repro.service.journal": 15.0, "repro.service.start": 1.0,
        "repro.service.execute": 2.0, "repro.stream.grid": 11.0,
        "repro.stream.fetch": 10.0, "repro.wire.encode": 5.0,
        "repro.wire.decode": 2.0, "bench.result": 18.0})
    assert sum(got.values()) == pytest.approx(70.0)      # all the idle
    # Each gap is named by its largest piece, not by its midpoint.
    assert [[n, round(s * 1e3, 6)] for n, s in r["idle_gaps"]] == [
        ["repro.service.journal", 25.0], ["repro.stream.grid", 20.0],
        ["bench.result", 15.0], ["repro.stream.fetch", 10.0]]
    # Admission (2 ms) to the end of the final frame's encoding (75 ms):
    # 48 ms idle, all but the 2 ms of bench.submit on a repro span.
    assert r["ticket_idle"]["tickets"] == 1
    assert r["ticket_idle"]["idle_s"] == pytest.approx(0.048)
    assert r["ticket_idle"]["repro_s"] == pytest.approx(0.046)


def test_the_worker_thread_comes_first_when_threads_overlap():
    events = served() + [span(C, "repro.service.admit", 41, 2,
                              client_id="c2")]
    got = S.reduce(events)["idle_by_span"]
    assert got["repro.stream.fetch"] == pytest.approx(0.010)
    assert got["repro.service.admit"] == pytest.approx(0.002)
    # Without the worker's spans the other thread's innermost wins.
    bare = [e for e in events if len(e) < 7 or e[6] != W]
    assert S.reduce(bare)["idle_by_span"]["repro.service.admit"] \
        == pytest.approx(0.004)


def test_scope_time_is_self_time_of_the_step_ops():
    r = S.reduce(served(), op_paths=OP_PATHS)
    # The loop's 20 ms less the 15 ms of its nested ops; the dense
    # evaluator's eq1_11 is not the step's.
    assert r["scope_s"] == pytest.approx({
        "unscoped": 0.005, "eq1_11": 0.010, "dominance_filter": 0.005,
        "fold_chunk": 0.005})
    assert r["scope_leaf_s"] == pytest.approx({
        "eq1_11": 0.010, "dominance_filter": 0.005, "fold_chunk": 0.005})
    assert S.scope_of("a/chunk_partials/dominance_filter/b") \
        == "dominance_filter"
    assert S.scope_of("jit(step)/while") == S.UNSCOPED
    assert S.scope_of("x/eq1_11x/y") == S.UNSCOPED
    # Without the paths every step op is unscoped.
    assert S.reduce(served())["scope_s"] == {"unscoped":
                                             pytest.approx(0.025)}


def test_each_reader_of_the_spans():
    r = S.reduce(served(), op_paths=OP_PATHS)
    assert S.readings(r, RUN) == pytest.approx({
        "queue_wait_p50_s": 0.01,
        "journal_s_per_ticket": 0.015,
        "codec_s_per_ticket": 0.007,
        # the executor's 46 ms less the device's 25 ms inside it
        "executor_idle_p50_s": 0.021,
        "filter_device_s_per_gconfig": 0.005 / 0.5,
        "eval_device_s_per_gconfig": 0.010 / 0.5})
    assert S.readings(None, RUN) == {}
    assert S.readings(S.reduce(synthetic()), {"studies": []}) == {}
    fallback = reader("fallback_s_per_study")
    assert fallback({"studies": [{"stats": {"fallback_s": 0.3}},
                                 {"stats": {"fallback_s": 0.1}}]}) \
        == pytest.approx(0.2)
    assert fallback(RUN) is None        # a program without the span
    assert fallback({"studies": []}) is None


def test_program_spans_list_the_longest_with_thread_and_ticket():
    ps = S.reduce(served())["program_spans"]
    assert ps["count"] == 10
    assert ps["by_name"]["repro.service.journal"] == [
        3, pytest.approx(0.015)]
    name, thread, ticket, at, dur = ps["longest"][0]
    assert (name, thread, ticket) == ("repro.service.execute", W, "T1")
    assert (at, dur) == (pytest.approx(0.012), pytest.approx(0.048))


def test_a_trace_recorded_on_the_cpu_loads_as_trace_py_loads_it(tmp_path):
    jax = pytest.importorskip("jax")
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.runtime import spans

    f = jax.jit(lambda a: (a @ a).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("bench.window"):
        with spans.span("repro.stream.fetch", step=3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = S.load(str(tmp_path))
    assert S.trace_events(events) == T.load(str(tmp_path))
    (fetch,) = [e for e in events if e[2] == "repro.stream.fetch"]
    assert fetch[5] == {"step": 3} and fetch[6].startswith(fetch[1])
    assert S.reduce(events) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import tiny
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,metric", [("tiny.batch", "configs_per_s"),
                                         ("tiny.open", "ticket_p50_s")])
def test_the_traced_run_with_spans_leaves_run_py_as_it_was(root, cell,
                                                           metric):
    """``run.py --trace 1`` logs the traced end-to-end reading (a CPU
    trace has no device plane, so no span outputs) and puts back the
    step hook it watched the window's programs with."""
    import run
    import tiny

    from repro.core import backend as B

    hooks = (run.tracemod.load, run.read_metric, run.start_window,
             B.cached_step)
    out, lines = tiny.run_cell(root, cell, seed=3, trace=1)
    assert out["correct"] is True
    (e2e,) = [x["traced_end_to_end"] for x in lines
              if "traced_end_to_end" in x]
    assert e2e[metric] > 0
    assert (run.tracemod.load, run.read_metric, run.start_window,
            B.cached_step) == hooks


def test_op_paths_come_from_the_compiled_text_by_program_and_op():
    text = "\n".join([
        "HloModule jit_step, is_scheduled=true",
        '  %while.1 = (s64[]) while(%t), body=%b, metadata={op_name='
        '"jit(step)/while"}',
        '  ROOT %fusion.a = f64[8]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(step)/while/body/eq1_11/mul" '
        'source_file="x.py"}',
        "  %fusion.b = f64[8]{0} fusion(%p), kind=kLoop, "
        'metadata={op_name="jit(step)/chunk_partials/dominance_filter/lt"}',
        "  %copy.1 = f64[8]{0} copy(%p)"])
    paths = S.hlo_op_paths(text)
    assert paths == {
        ("jit_step", "while.1"): "jit(step)/while",
        ("jit_step", "fusion.a"): "jit(step)/while/body/eq1_11/mul",
        ("jit_step", "fusion.b"):
            "jit(step)/chunk_partials/dominance_filter/lt"}
    got = S.reduce(served(), op_paths=paths)["scope_s"]
    assert got == pytest.approx({"unscoped": 0.010, "eq1_11": 0.010,
                                 "dominance_filter": 0.005})


NEW_READERS = {
    "tiny.batch": ("filter_device_s_per_gconfig", "eval_device_s_per_gconfig"),
    "tiny.open": ("queue_wait_p50_s", "journal_s_per_ticket",
                  "codec_s_per_ticket", "executor_idle_p50_s")}


@pytest.mark.parametrize("cell", list(NEW_READERS))
def test_the_span_readers_read_a_recorded_trace_as_readings_does(
        root, monkeypatch, cell):
    """A traced run on the CPU records the program's and the harness's
    host spans; a CPU trace has no device plane, so one is added: inside
    each ``repro.stream.grid`` span, a step op under ``eq1_11`` for a
    quarter of the span and one under ``dominance_filter`` for a tenth.
    The step program is compiled once more for its op-name paths, two of
    which name those ops.  Each new reader then reads what
    ``trace_spans.readings`` gives of the run's spans."""
    import jax
    import tiny

    plane = T.plane_name(jax.devices("cpu")[0])
    load, hlo_op_paths = S.load, S.hlo_op_paths
    compiled = {}

    def with_device_plane(log_dir):
        events = load(log_dir)
        for _, _, name, s, d, *_ in list(events):
            if name != "repro.stream.grid":
                continue
            prog = {"program": "jit_step"}
            events += [
                (plane, T.MODULE_LINE, "jit_step(1)", s + 0.2 * d, 0.5 * d),
                (plane, T.OP_LINE, "fusion.eval", s + 0.2 * d, 0.25 * d,
                 prog, T.OP_LINE),
                (plane, T.OP_LINE, "fusion.filter", s + 0.5 * d, 0.1 * d,
                 prog, T.OP_LINE)]
        return events

    def with_two_ops(text):
        paths = hlo_op_paths(text)
        compiled.update(paths)
        return {**paths,
                ("jit_step", "fusion.eval"): "jit(step)/eq1_11/mul",
                ("jit_step", "fusion.filter"):
                    "jit(step)/chunk_partials/dominance_filter/lt"}

    monkeypatch.setattr(S, "load", with_device_plane)
    monkeypatch.setattr(S, "hlo_op_paths", with_two_ops)
    out, lines = tiny.run_cell(root, cell, seed=7, trace=1)
    assert out["correct"] is True
    # The window's step programs were compiled again, and their text
    # names the step's scopes.
    scopes = {S.scope_of(p) for p in compiled.values()}
    assert {"eq1_11", "dominance_filter", "decode_gather"} <= scopes
    (want,) = [x["span_readings"] for x in lines if "span_readings" in x]
    for name in NEW_READERS[cell]:
        assert out["metrics"][name]["value"] == want[name] > 0, name
    assert out["breakdown"]["idle_gaps"]
    if cell == "tiny.batch":
        assert want["filter_device_s_per_gconfig"] == pytest.approx(
            0.4 * want["eval_device_s_per_gconfig"])
    else:
        assert want["executor_idle_p50_s"] > 0
        assert any(g[0].startswith("repro.")
                   for g in out["breakdown"]["idle_gaps"])


def test_the_window_step_compiles_again_from_the_cache(tmp_path):
    """The step program a batch window runs, compiled once more after the
    window for its op-name paths, is a hit in the persistent compilation
    cache: its arguments' weak types and committed shardings are kept,
    which its key holds."""
    import json

    import jax
    import numpy as np
    import run
    import traffic as TM

    from jax.experimental.compilation_cache import compilation_cache
    from repro.core import stream

    config = json.loads((BENCH / "configs"
                         / "ht-partition-1e7.json").read_text())
    study, budgets = TM.draw_study(config, np.random.default_rng(1))
    kw = dict(TM.stream_kwargs(study), constraints=budgets,
              objectives=tuple(config["objectives"]),
              top_k=config["top_k"], devices=jax.devices()[:1])
    plan = stream.plan_stream(**kw)
    assert plan.scan > 1                     # the batch step, jit(step)
    quantum = plan.chunk * plan.scan
    events = []

    def listen(event, **_):
        events.append(event)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(tmp_path), 0, 0)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        stream.stream_grid(**kw, flat_range=(plan.n_total - 2 * quantum,
                                             plan.n_total))
        watch = run.StepWatch()
        try:
            stream.stream_grid(**kw, flat_range=(
                plan.n_total - 3 * quantum, plan.n_total))
        finally:
            watch.close()
        jax.monitoring.register_event_listener(listen)
        try:
            paths = watch.op_paths()
        finally:
            jax.monitoring.unregister_event_listener(listen)
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert len(watch.steps) == 1
    assert "/jax/compilation_cache/cache_hits" in events
    assert "/jax/compilation_cache/cache_misses" not in events
    assert {"eq1_11", "dominance_filter"} <= {S.scope_of(p)
                                              for p in paths.values()}


def _stack_self_times(ops):
    """The op-by-op walk ``trace_spans._self_times`` replaced."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    own = [o[1] - o[0] for o in ops]
    leaf = [True] * len(ops)
    stack = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(e, ops[p][1]) - s
            leaf[p] = False
        stack.append(i)
    return [(ops[i][2], own[i], leaf[i]) for i in range(len(ops))]


@pytest.mark.parametrize("nested", [True, False])
def test_self_times_match_an_op_by_op_walk(nested):
    """Nested ops as a loop and its body give them, and ops that only
    overlap, with ties of start and end."""
    import numpy as np

    rng = np.random.default_rng(5)
    if nested:
        ops, t = [], 0.0
        for _ in range(300):
            n = int(rng.integers(0, 4))
            ops.append((t, t + 10.0 * (n + 1)))
            ops += [(t + 10.0 * i, t + 10.0 * i + rng.integers(1, 11))
                    for i in range(n)]
            t += 10.0 * (n + 1) + rng.integers(0, 3)
    else:
        s = rng.integers(0, 500, 2000).astype(float)
        ops = list(zip(s, s + rng.integers(1, 40, 2000)))
    s0 = np.array([o[0] for o in ops])
    s1 = np.array([o[1] for o in ops])
    order, own, leaf = S._self_times(s0, s1)
    want = _stack_self_times([(a, b, i) for i, (a, b) in enumerate(ops)])
    assert order.tolist() == [w[0] for w in want]
    assert own.tolist() == [w[1] for w in want]
    assert leaf.tolist() == [w[2] for w in want]
