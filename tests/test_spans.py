"""Profiler spans and named scopes of the engine.

A CPU profiler trace of one study and one served ticket holds the
``repro.*`` host spans of every layer boundary, nested as the layers
nest and carrying the ticket id; ``stream_grid``'s clocks are the
durations of its spans; the compiled chunk step names its five stages.
"""

import contextlib
import glob
import os
import warnings
from collections import defaultdict

import jax
import pytest

from repro.core import backend as B
from repro.core import stream
from repro.core.client import SweepClient
from repro.core.service import SweepRequest, SweepService
from repro.runtime import SweepServer
from repro.runtime import spans as SP

# Two chunks of 97 over 192 configurations, as in test_transport.
GRID = dict(
    agg_nodes=("7nm", "16nm"),
    sensor_nodes=("7nm", "16nm"),
    detnet_fps=tuple(float(f) for f in range(5, 65, 5)),
    keynet_fps=(30.0, 45.0),
    num_cameras=(2.0, 4.0),
)
CHUNK = 97
SCOPES = ("decode_gather", "eq1_11", "chunk_partials", "dominance_filter",
          "fold_chunk")


def _request(**grid):
    return SweepRequest(grid={**GRID, **grid}, chunk_size=CHUNK, top_k=4)


def _host_spans(log_dir):
    """``(thread, name, start_ns, end_ns, metadata)`` of every
    ``repro.*`` span in the newest trace under ``log_dir``; ``thread``
    is the line's index on its host plane."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    with warnings.catch_warnings():
        # JAX's stats type warns once, as it is first built.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("repro."):
                        out.append((i, e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _within(inner, outer):
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One study (survivor cap 1, so every chunk takes the dense
    fallback; a checkpoint every step) and one ticket served over a Unix
    socket, both traced after a warm-up outside the trace."""
    root = tmp_path_factory.mktemp("spans")
    svc = SweepService(spool_dir=str(root / "spool"), snapshot_every_s=0.0)
    server = SweepServer(svc, unix_path=str(root / "svc.sock"),
                         heartbeat_s=0.1, own_service=True).start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with SweepClient(server.address, reconnect_timeout_s=10.0) as cli:
            stream.stream_grid(**GRID, chunk_size=CHUNK)
            cli.submit(_request(), client_id="warm").result(timeout=120)
            jax.profiler.start_trace(str(root / "trace"),
                                     profiler_options=opts)
            try:
                with pytest.MonkeyPatch.context() as mp:
                    mp.setattr(stream, "_SURVIVOR_CAP", 1)
                    solo = stream.stream_grid(
                        **GRID, chunk_size=CHUNK,
                        checkpoint_dir=str(root / "ckpt"),
                        checkpoint_every_steps=1)
                # Other axis values than the warm-up's: a job of its
                # own, not a resume from the warm-up's checkpoint.
                tk = cli.submit(_request(keynet_fps=(30.0, 60.0)),
                                client_id="traced")
                served = tk.result(timeout=120)
                # The connection's thread closes the encode span after
                # its send; its answer to the next call on the same
                # connection comes after that.
                cli.ping()
            finally:
                jax.profiler.stop_trace()
    finally:
        server.close(drain=False, timeout=10.0)
    return solo, served, tk.id, _host_spans(str(root / "trace"))


def test_a_study_opens_every_executor_span(traced):
    solo, _, _, spans = traced
    (grid,) = [s for s in spans if s[1] == "repro.stream.grid"
               and "ticket" not in s[4]]
    (plan,) = [s for s in spans if s[1] == "repro.stream.plan"]
    assert plan[3] <= grid[2]
    # The study's spans, on the calling and the producer thread.
    study = [plan] + [s for s in spans if grid[2] <= s[2] <= grid[3]]
    names = {s[1] for s in study}
    assert names >= {"repro.stream.plan", "repro.stream.grid",
                     "repro.stream.probe", "repro.stream.dispatch",
                     "repro.stream.fetch", "repro.stream.merge",
                     "repro.stream.fallback", "repro.stream.checkpoint"}
    steps = int(solo.stats["n_chunks"])
    fetch = [s for s in study if s[1] == "repro.stream.fetch"]
    assert sorted(s[4]["step"] for s in fetch) == list(range(steps))
    fallback = [s for s in study if s[1] == "repro.stream.fallback"]
    assert len(fallback) == solo.stats["fallback_chunks"] > 0
    chunks = [s[4]["chunk"] for s in fallback]
    assert len(set(chunks)) == len(chunks)


def test_a_served_ticket_nests_its_spans_and_carries_its_id(traced):
    _, served, tid, spans = traced

    def named(name, **meta):
        return [s for s in spans if s[1] == name
                and all(s[4].get(k) == v for k, v in meta.items())]

    # The client resubmits its id before it watches: two admissions.
    assert len(named("repro.service.admit", client_id="traced")) == 2
    (start,) = named("repro.service.start", ticket=tid)
    assert start[4]["queued_s"] >= 0.0
    assert named("repro.service.plan")
    (execute,) = named("repro.service.execute", ticket=tid)
    (grid,) = named("repro.stream.grid", ticket=tid)
    assert _within(grid, execute)
    fetch = [s for s in named("repro.stream.fetch") if _within(s, grid)]
    assert len(fetch) == int(served.stats["n_chunks"])
    journal = named("repro.service.journal", ticket=tid)
    assert {s[4]["state"] for s in journal} == {"queued", "running",
                                                "done"}
    assert any(_within(s, start) for s in journal)
    (encode,) = named("repro.wire.encode", ticket=tid)
    (decode,) = named("repro.wire.decode", ticket=tid)
    assert execute[3] <= encode[2] <= decode[2]


def test_the_executor_clocks_are_the_durations_of_their_spans(
        monkeypatch, tmp_path):
    """Every stat a span times is the sum of that span's ``timed``
    durations, and nothing else adds to it."""
    real = SP.timed
    seen = defaultdict(float)
    keys = {}

    @contextlib.contextmanager
    def spy(acc, key, name, **ids):
        before = acc[key]
        with real(acc, key, name, **ids) as t0:
            yield t0
        seen[key] += acc[key] - before
        keys.setdefault(key, set()).add(name)

    monkeypatch.setattr(SP, "timed", spy)
    monkeypatch.setattr(stream, "_SURVIVOR_CAP", 1)
    for prefetch in (0, 2):
        seen.clear()
        res = stream.stream_grid(**GRID, chunk_size=CHUNK,
                                 prefetch=prefetch,
                                 checkpoint_dir=str(tmp_path / str(prefetch)),
                                 checkpoint_every_steps=1)
        for key in ("total_s", "device_wait_s", "host_merge_s",
                    "dispatch_s", "fallback_s", "checkpoint_write_s"):
            assert res.stats[key] == pytest.approx(seen[key], rel=1e-12)
            assert res.stats[key] > 0.0
        assert res.stats["fallback_s"] <= res.stats["host_merge_s"]
    assert keys == {"total_s": {"repro.stream.grid"},
                    "device_wait_s": {"repro.stream.fetch"},
                    "host_merge_s": {"repro.stream.merge"},
                    "dispatch_s": {"repro.stream.dispatch"},
                    "fallback_s": {"repro.stream.fallback"},
                    "checkpoint_write_s": {"repro.stream.checkpoint"}}


def test_the_compiled_step_names_its_five_stages(monkeypatch):
    """The op-name metadata of the chunk step, which a device trace's
    ops carry, holds every scope; numbers are unchanged by them (the
    bitwise parity tests of test_backend and test_stream)."""
    real = B.cached_step
    texts = []

    def spy(spec, backend=None, scan_chunks=1, n_dev=1, devices=None):
        fn = real(spec, backend, scan_chunks, n_dev, devices)

        def run(*args):
            if not texts:
                texts.append(fn.lower(*args).compile().as_text())
            return fn(*args)
        return run

    monkeypatch.setattr(B, "cached_step", spy)
    stream.stream_grid(**GRID, chunk_size=CHUNK)
    (text,) = texts
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
    assert "/chunk_partials/dominance_filter/" in text
